"""mx.trace tests: span round-trip + sampling arithmetic, the disabled
zero-allocation fast path, trainer/dataflow/block/checkpoint hook spans,
the skew probe surfaces (gauges, telemetry events, flight ring,
post-mortem section), the unified clock epoch, and the 2-rank acceptance
workflows — merged Perfetto trace validation and the seeded-straggler
verdict naming rank 1 as input-bound."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, dataflow, diagnostics, nd, parallel
from mxnet_tpu import telemetry, trace
from mxnet_tpu import util as mxutil
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAUNCH = os.path.join(ROOT, "tools", "launch.py")
TRACE_REPORT = os.path.join(ROOT, "tools", "trace_report.py")


@pytest.fixture(autouse=True)
def _clean_trace():
    yield
    trace.disable()
    trace.reset()
    telemetry.reset()
    telemetry.disable()
    diagnostics.uninstall()
    diagnostics.reset()
    config.reset()


def _trainer():
    parallel.make_mesh(dp=-1)
    net = nn.Dense(4, in_units=8)
    mx.random.seed(0)
    net.initialize()
    lfn = gloss.L2Loss()
    return parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                                   {"learning_rate": 0.1})


def _xy():
    return (nd.array(np.ones((8, 8), np.float32)),
            nd.array(np.zeros((8, 4), np.float32)))


# ---------------------------------------------------------------------------
# span round-trip + sampling arithmetic
# ---------------------------------------------------------------------------

def test_span_roundtrip_fields_and_meta(tmp_path):
    trace.enable(trace_dir=str(tmp_path), rank=3, sample_every=1)
    import time
    t0 = time.perf_counter()
    assert trace.record_span("step.dispatch", t0, t0 + 0.25, step=7,
                             cat="step", block="Dense")
    path = trace.flush()
    assert path == os.path.join(str(tmp_path), "3", "trace.jsonl")
    lines = [json.loads(line) for line in open(path)]
    meta, span = lines[0], lines[1]
    # meta first: the clock anchor trace_report aligns ranks with
    assert meta["kind"] == "meta" and meta["schema"] == 1
    assert meta["rank"] == 3
    assert meta["epoch_unix_ns"] == mxutil.epoch_unix_ns()
    assert meta["sample_every"] == 1
    assert span == {"kind": "span", "name": "step.dispatch",
                    "cat": "step", "ts_us": span["ts_us"],
                    "dur_us": 250000.0, "rank": 3, "step": 7,
                    "block": "Dense"}
    # the span timestamp sits on the shared monotonic epoch
    assert 0 <= span["ts_us"] <= mxutil.now_us()
    # flush() appends, meta only once
    trace.record_span("step.dispatch", t0, t0 + 0.1, step=14, cat="step")
    trace.flush()
    lines = [json.loads(line) for line in open(path)]
    assert [rec["kind"] for rec in lines] == ["meta", "span", "span"]


def test_failed_flush_keeps_spans_buffered(tmp_path):
    # an unwritable trace_dir must not LOSE spans: flush() promises (via
    # _safe_flush's warning) that they stay buffered for a later retry
    blocker = tmp_path / "blocker"
    blocker.write_text("")   # a FILE where the rank dir should go
    trace.enable(trace_dir=str(blocker), rank=0, sample_every=1)
    import time
    t0 = time.perf_counter()
    trace.record_span("step.dispatch", t0, t0 + 0.1, step=1, cat="step")
    with pytest.raises(OSError):
        trace.flush()
    assert [s["name"] for s in trace.spans()] == ["step.dispatch"]
    # a retry to a writable target succeeds WITH the meta line first
    good = tmp_path / "good" / "trace.jsonl"
    trace.flush(str(good))
    kinds = [json.loads(line)["kind"] for line in open(good)]
    assert kinds == ["meta", "span"]


def test_meta_line_is_per_target(tmp_path):
    # an explicit flush(path) to a side file (the documented in-memory
    # peek) must not rob the rank file of its meta line — the epoch
    # anchor trace_report aligns ranks with is tracked per target
    trace.enable(trace_dir=str(tmp_path), rank=0, sample_every=1)
    import time
    t0 = time.perf_counter()
    trace.record_span("step.dispatch", t0, t0, step=1, cat="step")
    side = tmp_path / "peek.jsonl"
    trace.flush(str(side))
    trace.record_span("step.dispatch", t0, t0, step=2, cat="step")
    rank_file = trace.flush()
    for p in (side, rank_file):
        kinds = [json.loads(line)["kind"] for line in open(p)]
        assert kinds[0] == "meta", (str(p), kinds)


def test_sampling_arithmetic_step_and_stream():
    trace.enable(sample_every=4)
    import time
    t0 = time.perf_counter()
    # step-keyed spans: only multiples of sample_every record
    recorded = [s for s in range(1, 9)
                if trace.record_span("step.fence", t0, t0, step=s,
                                     cat="step")]
    assert recorded == [4, 8]
    assert trace.sampled(4) and not trace.sampled(5)
    # step-less stream spans: per-name counter, first then every 4th
    got = [trace.record_span("input.batch_wait", t0, t0, cat="input")
           for _ in range(8)]
    assert got == [True, False, False, False, True, False, False, False]
    # always-spans (compiles, checkpoints) ignore sampling entirely
    assert trace.record_span("compile", t0, t0, step=5, cat="compile",
                             always=True)


def test_disabled_fast_path_zero_calls_and_zero_alloc(monkeypatch):
    assert not trace.enabled()
    assert trace._buf is None
    calls = {"span": 0, "skew": 0, "ann": 0}
    real = (trace.record_span, trace.skew_tick, trace.annotate)
    monkeypatch.setattr(trace, "record_span", lambda *a, **k: (
        calls.__setitem__("span", calls["span"] + 1), real[0](*a, **k))[1])
    monkeypatch.setattr(trace, "skew_tick", lambda *a, **k: (
        calls.__setitem__("skew", calls["skew"] + 1), real[1](*a, **k))[1])
    monkeypatch.setattr(trace, "annotate", lambda *a, **k: (
        calls.__setitem__("ann", calls["ann"] + 1), real[2](*a, **k))[1])
    tr = _trainer()
    x, y = _xy()
    for d, l in dataflow.prefetch_to_mesh(iter([([x], [y])] * 3), tr,
                                          depth=2):
        tr.step(d, l)
    net2 = nn.Dense(4, in_units=8)
    net2.initialize()
    net2.hybridize()
    net2(x)
    assert calls == {"span": 0, "skew": 0, "ann": 0}
    assert trace._buf is None, "disabled path allocated the span buffer"
    assert trace.spans() == []


# ---------------------------------------------------------------------------
# hook-site spans
# ---------------------------------------------------------------------------

def test_trainer_and_dataflow_spans(tmp_path):
    config.set("trace_skew_every", 2)
    trace.enable(trace_dir=str(tmp_path), rank=0, sample_every=1)
    # a trace never fences for itself: `step.fence` is recorded where
    # another observer (telemetry here) already fenced
    telemetry.enable()
    tr = _trainer()
    x, y = _xy()
    for d, l in dataflow.prefetch_to_mesh(iter([([x], [y])] * 4), tr,
                                          depth=2):
        tr.step(d, l)
    trace.flush()
    lines = [json.loads(line)
             for line in open(os.path.join(str(tmp_path), "0",
                                           "trace.jsonl"))]
    names = {}
    for rec in lines:
        if rec["kind"] == "span":
            names[rec["name"]] = names.get(rec["name"], 0) + 1
    # the compile step records ONE compile span (dispatch would be
    # compile-dominated); warm steps record dispatch + fence pairs
    assert names["step.compile"] == 1
    assert names["step.dispatch"] == 3 and names["step.fence"] == 3
    assert names["train.step"] == 4
    assert names["input.batch_wait"] == 4
    assert names["input.h2d_stage"] == 4
    steps = sorted({rec["step"] for rec in lines
                    if rec["kind"] == "span" and rec["name"] ==
                    "step.dispatch"})
    assert steps == [2, 3, 4]
    # skew probes fired every 2 sampled steps, wall-stamped for the
    # offline cross-rank match
    skews = [rec for rec in lines if rec["kind"] == "skew"]
    assert [s["step"] for s in skews] == [2, 4]
    assert all(s["t_wall_ns"] > 0 and s["participants"] == 1
               for s in skews)


def test_block_compile_and_checkpoint_spans(tmp_path):
    from mxnet_tpu import resilience
    trace.enable(trace_dir=str(tmp_path), rank=0, sample_every=1000)
    # sample_every huge: compile/checkpoint spans must record anyway
    net = nn.Dense(4, in_units=8)
    mx.random.seed(0)
    net.initialize()
    net.hybridize()
    x, _ = _xy()
    net(x)
    tr = _trainer()
    y = nd.array(np.zeros((8, 4), np.float32))
    tr.step(x, y)
    resilience.enable()
    try:
        mgr = resilience.CheckpointManager(tr, str(tmp_path / "ck"))
        mgr.save()
    finally:
        resilience.uninstall()
    names = [s["name"] for s in trace.spans()]
    assert "compile" in names, names
    assert "step.compile" in names, names
    assert "checkpoint.save" in names, names
    # nothing ELSE recorded at this sampling stride
    assert "step.dispatch" not in names and "input.batch_wait" not in names


# ---------------------------------------------------------------------------
# live(): the off path, arming by a profiler session, never fencing
# ---------------------------------------------------------------------------

def _paged_server(slots=2):
    from mxnet_tpu import serve
    from mxnet_tpu.models import gpt as gpt_mod
    parallel.make_mesh(dp=-1)
    model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    mx.random.seed(0)
    model.initialize()
    srv = serve.Server(model, slots=slots, page_size=8, prefill_chunk=8,
                       buckets=[32])
    rng = np.random.RandomState(0)
    for n_prompt in (11, 5, 9):
        srv.submit(rng.randint(0, 128, (n_prompt,)).astype(np.int32),
                   max_new_tokens=6, eos=None, temperature=0.0)
    return srv


def _count_calls(monkeypatch, *names):
    calls = dict.fromkeys(names, 0)

    def counting(name, real):
        def wrapper(*a, **k):
            calls[name] += 1
            return real(*a, **k)
        return wrapper

    for name in names:
        monkeypatch.setattr(trace, name, counting(name, getattr(trace, name)))
    return calls


@pytest.mark.parametrize("path", ["serve", "train"])
def test_off_path_is_one_live_call_a_step(monkeypatch, path):
    """With tracing off and no profiler session a step costs one
    `live()` and nothing else of mx.trace: no span, no record, no
    annotation, no TraceAnnotation, no buffer."""
    assert not trace.live() and trace._buf is None
    if path == "serve":
        srv = _paged_server()
        srv.step()                       # compile outside the count

        def step():
            srv.step()
    else:
        tr = _trainer()
        x, y = _xy()
        tr.step_async(x, y)

        def step():
            tr.step_async(x, y)
    calls = _count_calls(monkeypatch, "live", "span", "record_span",
                         "annotate", "skew_tick", "_TraceMe")
    rows0 = trace._steps_written
    n = 5
    for _ in range(n):
        step()
    # the step table is the one thing a serving step writes when not live
    assert trace._steps_written - rows0 == (n if path == "serve" else 0)
    if path == "serve":
        st = srv.stats()
        srv.stop()
        # a step is one pass, but where a request's chunk (8) is wider
        # than the wide pass (2 x 2 slots) and takes a second
        assert st["chunk_steps"] + st["token_steps"] \
            == st["chunk_dispatches"] >= n + 1
        assert st["scheduler_steps"] == n + 1
        assert st["chunk_steps"] >= 2 and st["token_steps"] >= 1
    assert calls == {"live": n, "span": 0, "record_span": 0, "annotate": 0,
                     "skew_tick": 0, "_TraceMe": 0}
    assert trace._buf is None and trace.spans() == []


def _inside(inner, outer, slack=0.0):
    return outer[0] - slack <= inner[0] \
        and inner[0] + inner[1] <= outer[0] + outer[1] + slack


def test_profiler_session_arms_the_spans(tmp_path):
    """`jax.profiler.start_trace` alone (mx.trace never enabled) puts the
    program's spans into `spans()` and into the .xplane.pb, nested as
    documented; they stop when the session ends."""
    import glob

    import jax
    from jax.profiler import ProfileData
    srv = _paged_server()
    tr = _trainer()
    x, y = _xy()
    srv.step()
    tr.step_async(x, y)                          # compiles, untraced
    assert not trace.enabled() and trace.spans() == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.live()
        for _ in range(3):
            srv.step()
        for d, l in dataflow.prefetch_to_mesh(iter([([x], [y])] * 3), tr,
                                              depth=2):
            loss = tr.step_async(d, l)
        loss.asnumpy()
    finally:
        jax.profiler.stop_trace()
    assert not trace.live()
    recorded = trace.spans()
    srv.step()
    tr.step_async(x, y)
    srv.stop()
    assert len(trace.spans()) == len(recorded), "spans after the session"
    serving = ["serve.step", "serve.schedule", "serve.prepare",
               "serve.decode_step", "serve.fetch", "serve.stream"]
    training = ["train.step", "step.dispatch", "input.batch_wait",
                "input.h2d_stage"]
    by_name = {}
    for sp in recorded:
        by_name.setdefault(sp["name"], []).append(sp)
    # one of each a step; of the four that make a pass, one a pass (a
    # request's chunk of 8 is two passes of the wide width, 2 x 2 slots)
    per_pass = serving[2:]
    passes = len(by_name["serve.decode_step"])
    assert passes >= 3
    for name in serving + training:
        assert len(by_name.get(name, ())) \
            == (passes if name in per_pass else 3), (name, sorted(by_name))
    assert "step.fence" not in by_name

    def iv(sp):
        return sp["ts_us"], sp["dur_us"]

    # the buffer's nesting, step by step
    for k, step in enumerate(by_name["serve.step"]):
        inside = [sp for name in serving[1:] for sp in by_name[name]
                  if sp["step"] == step["step"]]
        assert {sp["name"] for sp in inside} == set(serving[1:])
        for sp in inside:
            assert _inside(iv(sp), iv(step), 0.2), sp["name"]
        rounds = [sp for sp in inside if sp["name"] == "serve.decode_step"]
        fetches = [sp for sp in inside if sp["name"] == "serve.fetch"]
        assert len(rounds) == len(fetches)
        for rnd, fetch in zip(rounds, fetches):
            assert _inside(iv(fetch), iv(rnd), 0.2)
        assert step["chunk"] == max(sp["chunk"] for sp in rounds)
        assert _inside(iv(by_name["step.dispatch"][k]),
                       iv(by_name["train.step"][k]), 0.2)
    assert all(sp["tokens"] >= 0 for sp in by_name["serve.stream"])
    assert all("admitted" in sp for sp in by_name["serve.schedule"])
    # the same spans in the profiler's own file, on its clock
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in serving + training:
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns))
    # (the wait for the end of the stream is in the file too; the buffer
    # leaves it out, as it is no input stall)
    assert len(events.pop("input.batch_wait")) == 4
    for name in set(serving + training) - {"input.batch_wait"}:
        assert len(events.get(name, ())) \
            == (passes if name in per_pass else 3), (name, sorted(events))
    for name in serving[1:]:
        for child in events[name]:
            assert any(_inside(child, step)
                       for step in events["serve.step"]), name
    for fetch in events["serve.fetch"]:
        assert any(_inside(fetch, rnd)
                   for rnd in events["serve.decode_step"])
    for k in range(3):
        assert _inside(sorted(events["step.dispatch"])[k],
                       sorted(events["train.step"])[k])


def test_late_attrs_reach_the_capture(tmp_path):
    """Attrs set inside a span's block (`chunk` on `serve.step`,
    `admitted`, `tokens`, `bytes`, `rows`) are in the .xplane.pb's event
    stats, as they are in the buffer."""
    import glob

    import jax
    from jax.profiler import ProfileData
    srv = _paged_server()
    srv.step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            srv.step()
    finally:
        jax.profiler.stop_trace()
    srv.stop()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    late = {"serve.step": {"step", "chunk"},
            "serve.schedule": {"step", "admitted"},
            "serve.prepare": {"step", "slots", "chunk"},
            "serve.dispatch": {"step", "chunk", "width"},
            "serve.fetch": {"step", "chunk", "bytes", "rows"},
            "serve.stream": {"step", "tokens"}}
    seen = dict.fromkeys(late, 0)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in late:
                    assert late[e.name] <= {k for k, _ in e.stats}, e.name
                    seen[e.name] += 1
    assert all(seen.values()), seen


PHASES = ("schedule_s", "prepare_s", "dispatch_s", "fetch_s", "stream_s")


def test_step_table_one_row_a_step_joined_to_the_spans():
    """One row a `Server.step`, traced or not, with the id of the step's
    spans; its phases add up to no more than the step; a step that builds
    counts its compiles, a steady step none; every pass polls."""
    srv = _paged_server()
    srv.step()                          # builds the bucket: jit misses
    trace.enable(sample_every=1)
    n = 1 + srv.drain()
    srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=3, eos=None,
               temperature=0.0)
    n += srv.drain()                    # the same shapes again: steady
    srv.stop()
    rows = trace.steps()
    assert [r["step"] for r in rows] == list(range(1, n + 1))
    assert set(rows[0]) == set(trace.STEP_FIELDS)
    assert rows[0]["compiles"] >= 1 and rows[0]["compile_s"] > 0
    assert rows[-1]["compiles"] == 0 and rows[-1]["compile_s"] == 0
    assert sum(r["admitted"] for r in rows) == 4
    for r in rows:
        assert all(r[p] >= 0 for p in PHASES)
        assert sum(r[p] for p in PHASES) <= r["total_s"]
        if r["passes"]:
            assert r["polls"] >= r["passes"] and r["fed"] >= 1
            assert 0 <= r["poll_gap_max_s"] <= r["fetch_s"]
    by_step = {r["step"]: r for r in rows}
    spans = trace.spans()
    for sp in spans:
        if sp["name"] != "serve.step":
            continue
        r = by_step[sp["step"]]
        passes = [p for p in spans if p["name"] == "serve.decode_step"
                  and p["step"] == sp["step"]]
        assert r["passes"] == len(passes)
        assert r["fed"] == sum(p["fed"] for p in passes)
        assert r["wide"] == (sp.get("chunk", 1) > 1)
        # the row is stamped around the span, on the same epoch
        assert r["t0"] <= sp["ts_us"] + 0.1
        assert sp["ts_us"] + sp["dur_us"] <= r["t0"] + 1e6 * r["total_s"] \
            + 0.2


def test_step_table_walks_are_the_runs_the_passes_hand_the_kernel():
    """`walks`: the runs of one request's rows each step's passes hand the
    paged kernel, on the host. Steps that mix decoding rows with prompt
    chunks: every step's `walks` is its passes' feeds, and the kernel's
    own reading of the packed rows (`_runs`: consecutive positions through
    one table row) finds as many runs."""
    import importlib
    pa = importlib.import_module("mxnet_tpu.pallas_ops.paged_attention")
    srv = _paged_server(slots=3)
    handed = []
    real = srv._pass

    def watched(grp, feeds, *a, **k):
        tables = np.asarray(grp.device_tables())
        pos = [q for i, p, ids in feeds for q in range(p, p + len(ids))]
        rows = [tables[i] for i, p, ids in feeds for _ in ids]
        run = pa._runs(np.array(rows), np.array(pos, np.int32), 10 ** 6)[0]
        handed.append((len(feeds), int((np.asarray(run) > 0).sum()),
                       len(pos)))
        return real(grp, feeds, *a, **k)

    srv._pass = watched
    n = srv.drain()
    srv.stop()
    rows = trace.steps(tail=n)
    assert sum(r["passes"] for r in rows) == len(handed)
    assert all(f == runs for f, runs, _ in handed)
    k = 0
    for r in rows:
        mine = handed[k:k + r["passes"]]
        k += r["passes"]
        assert r["walks"] == sum(f for f, _, _ in mine)
        assert r["fed"] == sum(fed for _, _, fed in mine)
    # a step of chunks and decoding rows: fewer walks than rows fed
    assert any(r["walks"] < r["fed"] for r in rows)
    assert sum(r["walks"] for r in rows) < sum(r["fed"] for r in rows)


def test_step_table_drops_the_oldest_and_allocates_nothing():
    """A preallocated ring: past `MAX_STEPS` rows the oldest go, the
    array stays the one made at import, and writing a row keeps nothing."""
    import tracemalloc
    table = trace._steps
    row = [0.0] * len(trace.STEP_FIELDS)
    over = trace.MAX_STEPS + 100
    for k in range(over):
        row[0] = k + 1
        trace.note_step(row)
    held = trace.steps()
    assert len(held) == trace.MAX_STEPS
    assert held[0]["step"] == 101 and held[-1]["step"] == over
    assert [r["step"] for r in trace.steps(tail=3)] \
        == [over - 2, over - 1, over]
    assert trace.steps(tail=0) == []
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for k in range(20_000):
            row[0] = k
            trace.note_step(row)
        grown = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert trace._steps is table
    assert grown < 4096, grown
    trace.reset()
    assert trace.steps() == [] and trace._steps is table


def test_serve_dispatch_lies_in_decode_step_before_the_fetch():
    """`serve.dispatch`: one a pass, inside `serve.decode_step`, closed
    before the pass's `serve.fetch` opens."""
    trace.enable(sample_every=1)
    srv = _paged_server()
    srv.drain()
    srv.stop()
    spans = trace.spans()

    def iv(sp):
        return sp["ts_us"], sp["dur_us"]

    rounds = [sp for sp in spans if sp["name"] == "serve.decode_step"]
    dispatches = [sp for sp in spans if sp["name"] == "serve.dispatch"]
    fetches = [sp for sp in spans if sp["name"] == "serve.fetch"]
    assert len(rounds) == len(dispatches) == len(fetches) >= 3
    for rnd, disp, fetch in zip(rounds, dispatches, fetches):
        assert disp["step"] == rnd["step"] == fetch["step"]
        assert (disp["chunk"], disp["width"]) == (rnd["chunk"], rnd["width"])
        assert disp["cat"] == "phase"
        assert _inside(iv(disp), iv(rnd), 0.2)
        assert disp["ts_us"] + disp["dur_us"] <= fetch["ts_us"] + 0.2


def test_a_live_span_does_not_fence(monkeypatch):
    """mx.trace on, every fencing observer off: `step_async` waits for
    nothing and records no `step.fence`."""
    import jax
    from mxnet_tpu.parallel import trainer as trainer_mod
    trace.enable(sample_every=1)
    tr = _trainer()
    x, y = _xy()
    tr.step_async(x, y)
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(trainer_mod.jax, "block_until_ready",
                        lambda v: (waits.append(1), real(v))[1])
    for _ in range(3):
        tr.step_async(x, y)
    monkeypatch.undo()
    names = [sp["name"] for sp in trace.spans()]
    assert waits == [] and "step.fence" not in names
    assert names.count("train.step") == 4
    assert names.count("step.dispatch") == 3
    assert names.count("step.compile") == 1


def test_setup_table_and_scope_map():
    """The always-on set-up sums, and the program's map from HLO
    instruction names to its named scopes, built on demand."""
    before = trace.setup()
    assert set(before) == {"import_s", "initialize_s", "compile_s",
                           "compile_cache_hits", "compile_cache_misses"}
    srv = _paged_server()
    srv.step()
    tr = _trainer()
    x, y = _xy()
    tr.step_async(x, y)
    srv.stop()
    after = trace.setup()
    assert after["compile_s"] > before["compile_s"]
    assert after["initialize_s"] > before["initialize_s"]
    assert after["import_s"] == before["import_s"] > 0
    label = "serve.paged/bucket=32/chunk=8"
    ops = trace.scope_map(label)[label]
    for scope in ("kv_arena_update", "page_gather", "lm_head"):
        assert any(f"/{scope}/" in op for op in ops.values()), scope
    ops = trace.scope_map("train.step")["train.step"]
    joined = " ".join(ops.values())
    assert "jvp(forward)" in joined and "optimizer" in joined
    assert "transpose(jvp(forward))" in joined


def test_skew_cadence_is_step_keyed():
    # the probe is a blocking collective in multi-process gangs: its
    # cadence must be a pure function of the global step id, so a
    # rank-LOCAL extra tick (a jit-cache miss on a new bucket shape also
    # reaches skew_tick) cannot desynchronize which step each rank probes
    config.set("trace_skew_every", 2)
    trace.enable(sample_every=2)
    for step in (1, 2, 3, 3, 4, 5, 6, 7, 8):   # step 3 ticked twice
        trace.skew_tick(step)
    assert [s["step"] for s in trace.skews()] == [4, 8]


def test_buffer_bounded_with_unwritable_dir(tmp_path, monkeypatch):
    # an unwritable trace_dir (every flush failing and re-queuing) must
    # degrade to the same drop-oldest in-memory bound as the no-dir path
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    monkeypatch.setattr(trace, "_MAX_BUF", 10)
    monkeypatch.setattr(trace, "_FLUSH_EVERY", 5)
    monkeypatch.setattr(trace, "_flush_warned", True)  # warning once, tested above
    trace.enable(trace_dir=str(blocker), rank=0, sample_every=1)
    import time
    t0 = time.perf_counter()
    for s in range(1, 41):
        trace.record_span("step.fence", t0, t0, step=s, cat="step")
    snap = trace.snapshot()
    assert snap["spans_buffered"] <= 10
    assert snap["spans_dropped"] >= 30


def _trace_report_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("_trace_report_ut",
                                                  TRACE_REPORT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_verdict_overlapped_h2d_is_not_input_bound():
    # producer-side H2D staging overlaps device compute in the prefetch
    # worker: a healthy pipeline (long h2d_stage, zero batch_wait) must
    # NOT be called input-bound — only the consumer-visible stall counts
    tr_mod = _trace_report_module()
    healthy = {0: {"by_cat": {"input": 120e3, "step": 100e3},
                   "by_span": {"input.h2d_stage": 120e3,
                               "step.dispatch": 20e3,
                               "step.fence": 80e3},
                   "steps": [100e3]}}
    kind, _rank, dom, _detail = tr_mod._verdict(healthy, [])
    assert kind == "compute-bound" and dom == "step.fence"
    stalled = {0: {"by_cat": {"input": 500e3, "step": 100e3},
                   "by_span": {"input.batch_wait": 400e3,
                               "input.h2d_stage": 100e3,
                               "step.dispatch": 100e3},
                   "steps": [100e3]}}
    kind, rank, dom, _detail = tr_mod._verdict(stalled, [])
    assert kind == "input-bound" and rank == 0
    assert dom == "input.batch_wait"
    # a warmup window (all steps were cache misses -> only step.compile
    # spans, zero warm step time) with the genuine-but-incidental batch
    # wait of staging warmup is compile-bound, not input-bound
    warmup = {0: {"by_cat": {"input": 50e3, "compile": 5e6},
                  "by_span": {"input.batch_wait": 50e3,
                              "step.compile": 5e6},
                  "steps": []}}
    kind, rank, dom, _detail = tr_mod._verdict(warmup, [])
    assert kind == "compile-bound" and dom == "step.compile"


def test_trace_report_load_rebases_relaunched_generation(tmp_path):
    # launch.py --max-restarts: a relaunched worker appends a SECOND meta
    # with its own (later) epoch and spans whose ts_us restart near 0 —
    # the loader must rebase generation-2 records onto the first epoch so
    # they land at their true position, not overlapping generation 1
    tr_mod = _trace_report_module()
    d = tmp_path / "0"
    d.mkdir()
    e0 = 1_000_000_000_000_000
    lines = [
        {"kind": "meta", "schema": 1, "rank": 0, "epoch_unix_ns": e0},
        {"kind": "span", "name": "step.dispatch", "cat": "step",
         "ts_us": 100.0, "dur_us": 5.0, "rank": 0, "step": 1},
        {"kind": "meta", "schema": 1, "rank": 0,
         "epoch_unix_ns": e0 + 300_000_000_000},       # relaunch +300 s
        {"kind": "span", "name": "step.dispatch", "cat": "step",
         "ts_us": 50.0, "dur_us": 5.0, "rank": 0, "step": 1},
        {"kind": "skew", "ts_us": 60.0, "step": 2, "rank": 0,
         "t_wall_ns": 1, "participants": 1, "spread_s": 0.0,
         "straggler_rank": 0},
    ]
    (d / "trace.jsonl").write_text(
        "".join(json.dumps(rec) + "\n" for rec in lines))
    meta, spans, skews = tr_mod.load(str(d / "trace.jsonl"))
    assert meta["epoch_unix_ns"] == e0   # first meta anchors the rank
    assert spans[0]["ts_us"] == 100.0
    assert spans[1]["ts_us"] == 300e6 + 50.0
    assert skews[0]["ts_us"] == 300e6 + 60.0


def test_cross_rank_skews_do_not_mix_generations():
    # a resumed gang replays step ids: rank 0's post-restart stamp for
    # step 4 must not pair with dead rank 1's pre-restart stamp — that
    # would read the restart backoff (60 s here) as arrival skew
    tr_mod = _trace_report_module()
    t = 1_000_000_000_000_000_000
    ranks = {
        0: (None, [], [
            {"step": 4, "t_wall_ns": t, "gen": 0},
            {"step": 4, "t_wall_ns": t + 60_000_000_000, "gen": 1},
        ]),
        1: (None, [], [
            {"step": 4, "t_wall_ns": t + 1_000_000, "gen": 0},
        ]),
    }
    out = tr_mod.cross_rank_skews(ranks)
    assert len(out) == 1
    step, spread, straggler = out[0]
    assert step == 4 and straggler == 1
    assert abs(spread - 1e-3) < 1e-9


def test_trace_report_discover_unique_ranks(tmp_path):
    # two files claiming the same rank (or one with no digit component)
    # must not silently overwrite each other in the merge
    tr_mod = _trace_report_module()
    paths = []
    for sub in ("runA/1", "runB/1", "nodigit"):
        d = tmp_path / sub
        d.mkdir(parents=True)
        f = d / "trace.jsonl"
        f.write_text("")
        paths.append(str(f))
    got = tr_mod.discover(paths)
    ranks = [r for r, _ in got]
    assert len(set(ranks)) == 3, ranks
    assert ranks[0] == 1  # the first honest parse keeps its rank


def test_skew_probe_surfaces():
    telemetry.enable()
    diagnostics.enable()
    config.set("trace_skew_every", 1)
    trace.enable(sample_every=1)
    tr = _trainer()
    x, y = _xy()
    for _ in range(2):
        tr.step(x, y)
    # gauges fed (single participant: spread 0.0, straggler = own rank)
    assert telemetry.get("step_skew_seconds").value == 0.0
    assert telemetry.get("straggler_rank").value == 0.0
    # telemetry event stream + flight ring both carry the probe
    kinds = [e["kind"] for e in telemetry.events()]
    assert "trace_skew" in kinds
    ring = diagnostics.records("trace")
    assert ring and ring[-1]["straggler_rank"] == 0
    # post-mortem gets a "trace" section with the last probe
    pm = trace.snapshot()
    assert pm["skew_probes"] == 2 and pm["last_skew"]["step"] == 2
    assert trace.skew_p99_ms() is None  # 1 participant: no gang skew


def test_postmortem_trace_section(tmp_path):
    diagnostics.install(diagnostics_dir=str(tmp_path), rank=0)
    config.set("trace_skew_every", 1)
    trace.enable(sample_every=1)
    tr = _trainer()
    x, y = _xy()
    tr.step(x, y)
    path = diagnostics.dump(reason="manual")
    pm = json.load(open(path))
    assert pm["trace"]["skew_probes"] == 1
    assert pm["trace"]["sample_every"] == 1
    assert pm["trace"]["spans_recorded"] > 0


def test_unified_epoch():
    # clock unification: profiler scopes and telemetry events share the
    # trace epoch, so all three timelines have one zero point
    from mxnet_tpu import profiler
    assert abs(profiler._now_us() - mxutil.now_us()) < 1e6
    telemetry.enable()
    telemetry.event("step", dur_s=0.0)
    ev = telemetry.events()[-1]
    assert 0 < ev["mono_us"] <= mxutil.now_us()


def test_annotate_is_a_usable_context():
    trace.enable()
    with trace.annotate(5):
        pass  # TraceAnnotation is a no-op without an active XLA trace


def test_trace_report_single_rank(tmp_path):
    trace.enable(trace_dir=str(tmp_path), rank=0, sample_every=1)
    tr = _trainer()
    x, y = _xy()
    for _ in range(3):
        tr.step(x, y)
    trace.flush()
    r = subprocess.run(
        [sys.executable, TRACE_REPORT, str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "verdict" in r.stdout
    doc = json.load(open(os.path.join(str(tmp_path), "trace_merged.json")))
    assert {e["pid"] for e in doc["traceEvents"]} == {0}


# ---------------------------------------------------------------------------
# 2-rank acceptance workflows
# ---------------------------------------------------------------------------

_WORKER = """\
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {root!r})
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, dataflow, resilience, trace
from mxnet_tpu.gluon import nn, loss as gloss

rank = int(os.environ.get("JAX_PROCESS_ID", "0"))
total = int(sys.argv[1])
assert trace.enabled(), "launcher should have armed mx.trace"
resilience.enable()   # arms the fault injector from MXNET_TPU_FAULT_INJECT

parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                             {{"learning_rate": 0.1}})
rs = np.random.RandomState(0)
batches = [([nd.array(rs.randn(8, 8).astype(np.float32))],
            [nd.array(rs.randn(8, 4).astype(np.float32))])
           for _ in range(total)]
for d, l in dataflow.prefetch_to_mesh(iter(batches), tr, depth=1):
    tr.step(d, l)
trace.flush()
print(f"rank {{rank}} done at step {{tr.num_update}}")
"""


def _launch_two_ranks(tmp_path, fault=""):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(root=ROOT))
    trace_dir = tmp_path / "traces"
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PROCESS_ID", "MXNET_TPU_FAULT_INJECT",
                        "MXNET_TPU_TRACE", "MXNET_TPU_TRACE_DIR")}
    env.update({"MXNET_TPU_TRACE_SAMPLE_EVERY": "1",
                "MXNET_TPU_TRACE_SKEW_EVERY": "2",
                "JAX_PLATFORMS": "cpu"})
    if fault:
        env["MXNET_TPU_FAULT_INJECT"] = fault
    r = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--trace-dir", str(trace_dir),
         sys.executable, str(worker), "6"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return trace_dir


@pytest.mark.slow
def test_two_rank_merged_trace_validates(tmp_path):
    trace_dir = _launch_two_ranks(tmp_path)
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, TRACE_REPORT, str(trace_dir),
         "--out", str(out)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    # chrome-trace schema: every event carries ph/pid/ts (metadata 'M'
    # rows carry names), and both ranks have a named process track
    assert isinstance(evs, list) and evs
    names = {(e["pid"], e["args"]["name"]) for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert names == {(0, "rank 0"), (1, "rank 1")}
    spans = [e for e in evs if e.get("ph") == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["name"]
    # aligned epochs: both ranks' span timestamps land inside one short
    # shared window (a clock mix-up would offset them by the epoch gap)
    assert max(e["ts"] + e["dur"] for e in spans) < 120e6
    # per-rank step spans exist on both tracks with the same step ids
    step_ids = {pid: {e["args"]["step"] for e in spans
                      if e["pid"] == pid and "step" in e.get("args", {})
                      and e["cat"] == "step"}
                for pid in (0, 1)}
    assert step_ids[0] and step_ids[0] == step_ids[1]


@pytest.mark.slow
def test_two_rank_straggler_report_names_rank1(tmp_path):
    # FaultInjector stall_input on rank 1 only: its input pipeline stalls
    # 400 ms once, the gang verdict must name rank 1 as the input-bound
    # straggler with an input-side dominant span
    trace_dir = _launch_two_ranks(tmp_path,
                                  fault="stall_input:400@rank:1")
    r = subprocess.run(
        [sys.executable, TRACE_REPORT, str(trace_dir)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    verdicts = [line for line in r.stdout.splitlines()
                if "verdict:" in line]
    assert verdicts, r.stdout
    assert any("input-bound" in line and "straggler rank 1" in line
               for line in verdicts), r.stdout
    assert "input.batch_wait" in r.stdout
    # the measured cross-rank arrival skew names the same straggler
    assert "most-frequent straggler rank 1" in r.stdout
    # the merged Perfetto trace landed next to the rank files
    assert os.path.exists(os.path.join(str(trace_dir),
                                       "trace_merged.json"))
