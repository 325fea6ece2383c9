"""Tests of the readers that take per-layer metrics from the program's own
spans and scopes (chipbench/program_spans.py and the readers on it), on the
CPU: the clock join and each reader on a trace and a span list built by
hand, a program without the spans (the parent of the PR that added them),
and the rehearsal of each cell with the profiler on. No test claims a
device number.
"""
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_spans, xplane  # noqa: E402
from chipbench import run as bench_run  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
OFFSET_NS = 3_250_000_000.0      # trace clock minus program clock
STEP_NS, PERIOD_NS, STEPS = 9_000_000.0, 10_000_000.0, 4
CHUNKS = [8, 8, 1, 8]
SCOPES = {
    "serve.paged/bucket=256/chunk=8": {
        "copy.5": "jit(pure)/while/body/kv_arena_update/scatter",
        "paged_attention.3": "jit(pure)/while/body/page_gather/"
                             "paged_attention/pallas_call",
        "fusion.1": "jit(pure)/while/body/lm_head/dot_general"},
    "serve.paged/bucket=256/chunk=1": {
        "copy.5": "jit(pure)/while/body/closed_call/dot_general",
        "copy.9": "jit(pure)/while/body/page_gather/transpose"},
    "train.step": {
        "fusion.1": "jit(step)/jvp(forward)/dot_general",
        "copy.5": "jit(step)/transpose(jvp(forward))/transpose",
        "copy.9": "jit(step)/optimizer/pad",
        "paged_attention.3": "jit(step)/optimizer/lamb/pallas_call"},
}


def reader(name):
    return importlib.import_module("chipbench.readers." + name).read


def span(name, trace_start_ns, dur_ns, **attrs):
    return {"kind": "span", "name": name, "cat": "serve", "rank": 0,
            "ts_us": round((trace_start_ns - OFFSET_NS) / 1e3, 1),
            "dur_us": round(dur_ns / 1e3, 1), **attrs}


def hand_built(outer="serve.step", inside_ns=400.0, stick_out_ns=0.0):
    """(result, spans): four harness steps of 9 ms every 10 ms; the
    program's `outer` span starts 0.4 us inside each. On the device, a
    step runs fusion.1 (3 ms), copy.5 (2 ms), nothing for 1 ms, then
    paged_attention.3 (1 ms), and copy.9 (0.5 ms) after the step's span
    has closed."""
    host, spans, device = [], [], []
    for k in range(STEPS):
        t = 1_000_000.0 + k * PERIOD_NS
        host += [("bench.step", t, STEP_NS),
                 ("bench.refill", t + STEP_NS, 500_000.0)]
        s0 = t + inside_ns
        attrs = {"step": k + 1, "chunk": CHUNKS[k]}
        spans += [
            span("serve.schedule", s0 + 1_000, 100_000.0, step=k + 1,
                 admitted=0),
            span("serve.prepare", s0 + 200_000, 1_300_000.0, slots=32,
                 **attrs),
            span("serve.decode_step", s0 + 1_600_000, 6_500_000.0,
                 bucket=256, slots=32, **attrs),
            span("serve.stream", s0 + 8_150_000, 800_000.0, step=k + 1,
                 tokens=3),
            span(outer, s0, STEP_NS - 2 * inside_ns + stick_out_ns, **attrs),
            span("step.dispatch", s0 + 100_000, 700_000.0, step=k + 1),
            span("input.batch_wait", t + STEP_NS + 10_000, 50_000.0)]
        d = t + 1_700_000
        device += [("fusion.1", d, 3_000_000.0),
                   ("copy.5", d + 3_000_000, 2_000_000.0),
                   ("paged_attention.3", d + 6_000_000, 1_000_000.0),
                   ("copy.9", t + STEP_NS + 20_000, 500_000.0)]
    trace = xplane.Trace({"/device:TPU:0": device},
                         sorted(host, key=lambda e: e[1]))
    return {"trace": trace, "traced_steps": STEPS}, spans


@pytest.fixture
def program(monkeypatch):
    """Put a hand-built span list and scope map where the readers look."""
    def put(spans, scopes=SCOPES):
        monkeypatch.setattr(program_spans, "spans", lambda: spans)
        monkeypatch.setattr(program_spans, "scope_map",
                            lambda label: scopes.get(label, {}))
        program_spans._said.clear()
    return put


def test_clock_join_recovers_seconds_to_the_microsecond(capsys):
    result, spans = hand_built()
    program_spans._said.clear()     # said once a process, maybe already
    offset, residual = program_spans.join(result["trace"], spans,
                                          "serve.step")
    assert abs(offset - (OFFSET_NS - 400.0)) < 100.0      # rounding: 0.1 us
    assert residual < 1_000.0
    assert "residual" in capsys.readouterr().out
    # the last len(bench.step) spans pair up: older ones are left alone
    older = [span("serve.step", -5e9, 1e6, step=0, chunk=1)] + spans
    assert program_spans.join(result["trace"], older, "serve.step")[0] \
        == offset
    # mapped onto the trace's clock, every span lies in its bench.step
    bench = [e for e in result["trace"].host if e[0] == "bench.step"]
    for (_, s, d), (_, bs, bd) in zip(
            program_spans.mapped(spans, offset, {"serve.step"}), bench):
        assert bs - 1 <= s and s + d <= bs + bd + 1


def test_clock_join_refuses_a_residual_over_100_us(program):
    result, spans = hand_built(stick_out_ns=250_000.0)
    assert program_spans.join(result["trace"], spans, "serve.step") is None
    program(spans)
    for name, args in (
            ("program_span_ms_per_step",
             {"span": "serve.prepare", "outer": "serve.step"}),
            ("program_idle_ms_per_step",
             {"spans": ["serve.step"], "outer": "serve.step"}),
            ("program_span_share",
             {"span": "serve.step", "outer": "serve.step",
              "where": {"chunk": ["gt", 1]}})):
        assert reader(name)(result, **args) is None, name
    # no pairs at all: fewer program spans than harness steps
    assert program_spans.join(result["trace"], spans[:3], "serve.step") \
        is None
    assert program_spans.join(None, spans, "serve.step") is None


def test_span_readers_on_a_hand_built_stretch(program):
    result, spans = hand_built()
    program(spans)
    per_step = reader("program_span_ms_per_step")
    assert per_step(result, span="serve.schedule", outer="serve.step") \
        == pytest.approx(0.1)
    assert per_step(result, span="serve.prepare", outer="serve.step") \
        == pytest.approx(1.3)
    assert per_step(result, span="serve.stream", outer="serve.step") \
        == pytest.approx(0.8)
    assert per_step(result, span="serve.step", outer="serve.step",
                    stat="p50", where={"chunk": ["gt", 1]}) \
        == pytest.approx(8.9992, abs=1e-3)
    assert per_step(result, span="serve.step", outer="serve.step",
                    stat="p50", where={"chunk": ["eq", 1]}) \
        == pytest.approx(8.9992, abs=1e-3)
    assert per_step(result, span="serve.nosuch", outer="serve.step") is None
    share = reader("program_span_share")
    assert share(result, span="serve.step", outer="serve.step",
                 where={"chunk": ["gt", 1]}) == pytest.approx(75.0)
    # a span recorded before the stretch is not counted
    program([span("serve.prepare", -4e9, 5e9, step=0, chunk=8)] + spans)
    assert per_step(result, span="serve.prepare", outer="serve.step") \
        == pytest.approx(1.3)


def test_idle_reader_counts_gaps_inside_the_named_spans(program):
    result, spans = hand_built()
    program(spans)
    idle = reader("program_idle_ms_per_step")
    # a gap belongs to the span open at its middle. Inside serve.step: the
    # 1 ms hole before paged_attention, the 0.32 ms after it (the kernel
    # ends at 8.7 ms, copy.9 starts at 9.02, the span closes at 9.0), and
    # the 2.18 ms before the next step's first operation, whose middle
    # lies after that step's span has opened (three such)
    inside = (4 * 1.0 + 4 * 0.32 + 3 * 2.18) / 4
    assert idle(result, spans=["serve.step"], outer="serve.step") \
        == pytest.approx(inside)
    # input.batch_wait (50 us at 9.01 ms) holds the middle of no gap
    assert idle(result, spans=["serve.step", "input.batch_wait"],
                outer="serve.step") == pytest.approx(inside)
    assert idle(result, spans=["serve.nosuch"], outer="serve.step") is None


def test_scope_reader_tells_executables_apart_by_the_span(program):
    result, spans = hand_built()
    program(spans)
    scope = reader("trace_scope_ms_per_step")
    serve_args = {"label": "serve.paged/bucket={bucket}/chunk={chunk}",
                  "outer": "serve.step", "per_span": "serve.decode_step"}
    # copy.5 is the arena's in the chunk executable (3 of 4 steps), a
    # matmul's operand in the token executable; copy.9 runs after the
    # decode_step span closed, in no executable's window
    assert scope(result, scopes=["kv_arena_update", "page_gather"],
                 exclude=["paged_attention"], **serve_args) \
        == pytest.approx(3 * 2.0 / 4)
    assert scope(result, scopes=["kv_arena_update", "page_gather"],
                 **serve_args) == pytest.approx(3 * 3.0 / 4)
    assert scope(result, scopes=["lm_head"], **serve_args) \
        == pytest.approx(3 * 3.0 / 4)
    assert scope(result, scopes=["nosuch"], **serve_args) is None
    # one executable over the whole stretch (training)
    train_args = {"label": "train.step", "outer": "train.step"}
    assert scope(result, scopes=["forward", "jvp(forward)"], **train_args) \
        == pytest.approx(3.0)
    assert scope(result, scopes=["transpose(jvp(forward))"], **train_args) \
        == pytest.approx(2.0)
    assert scope(result, scopes=["optimizer"], **train_args) \
        == pytest.approx(1.5)
    # a `while` around the scoped events does not count them twice
    events = result["trace"].devices["/device:TPU:0"]
    events.append(("while.1", events[0][1] - 10, 7_500_000.0))
    assert scope(result, scopes=["forward", "jvp(forward)"], **train_args) \
        == pytest.approx(3.0)


def test_a_program_without_the_spans_reads_nothing(program, monkeypatch):
    result, _ = hand_built()
    program([], scopes={})
    for m in BENCH["per_layer"]:
        spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                                   m["name"] + ".json")
        if not spec["reader"].startswith(("program_span", "program_idle",
                                          "trace_scope")):
            continue
        assert reader(spec["reader"])(result, **spec["args"]) is None, m
    # the parent's mxnet_tpu.trace has neither setup() nor scope_map()
    from mxnet_tpu import trace
    monkeypatch.undo()
    monkeypatch.delattr(trace, "setup")
    monkeypatch.delattr(trace, "scope_map")
    assert reader("program_setup_s")(result, key="import_s") is None
    assert program_spans.scope_map("train.step") == {}


@pytest.fixture
def rehearsal(monkeypatch):
    """Run a cell's driver in this process as `--rehearsal --trace 1`
    does (tests/chipbench/test_chipbench.py has the same fixture)."""
    from mxnet_tpu import trace
    from mxnet_tpu.parallel import mesh as mesh_mod
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    before = mesh_mod._current["mesh"]
    trace.reset()

    def go(cell, seed, steps):
        bench, ctx = bench_run.context(cell, seed, 0.0, True, True,
                                       steps=steps)
        result = bench_run.driver_of(ctx).run(ctx)
        return result, bench_run.layer_metrics(bench, cell, result)

    yield go
    trace.reset()
    mesh_mod.set_mesh(before)


def synthetic_device(result, span_name, label):
    """A chip's event list for a rehearsal that ran on the CPU: inside
    each traced `span_name` span (put on the trace's clock by the join)
    one event of 10 us for every instruction of the executable's real
    scope map, back to back, with a 20 us hole after the third."""
    found = program_spans.in_stretch(
        result, "serve.step" if span_name.startswith("serve") else span_name)
    events = []
    for s in found[0]:
        if s["name"] != span_name:
            continue
        names = sorted(program_spans.scope_map(label.format(**s)))
        t = program_spans.start_ns(s) + found[1] + 1_000.0
        for i, op in enumerate(names[:400]):
            events.append((op, t, 10_000.0))
            t += 30_000.0 if i == 2 else 10_000.0
    return {"/device:TPU:0": events}


CELL_METRICS = {
    "gpt2-medium.decode-closed": {
        "serve.schedule_ms_per_step", "serve.prepare_ms_per_step",
        "serve.emit_ms_per_step", "serve.host_exposed_ms_per_step",
        "serve.chunk_step_ms_p50", "serve.token_step_ms_p50",
        "serve.chunk_step_share", "serve.kv_arena_ms_per_step",
        "setup.import_s", "setup.initialize_s", "setup.compile_s"},
    "bert-base.pretrain-1chip": {
        "train.dispatch_ms_per_step", "train.host_exposed_ms_per_step",
        "train.forward_ms_per_step", "train.backward_ms_per_step",
        "train.optimizer_ms_per_step",
        "setup.import_s", "setup.initialize_s", "setup.compile_s"},
}
CELL_METRICS["bert-base.pretrain-dp4"] = CELL_METRICS[
    "bert-base.pretrain-1chip"]


@pytest.mark.parametrize("cell", sorted(CELL_METRICS))
def test_rehearsal_with_the_profiler_on_yields_the_new_metrics(
        rehearsal, cell):
    """The profiler session of the traced stretch arms the program's
    spans with no edit to the harness. The CPU has no device plane, so
    the span metrics are read as they come and the device-trace ones
    from a chip's event list made up around the rehearsal's real spans
    and the real scope maps of its executables."""
    new = {m["name"] for m in BENCH["per_layer"]
           if bench_run.applies(m, cell)} & CELL_METRICS[cell]
    assert new == CELL_METRICS[cell]
    serving = cell.startswith("gpt2")
    result, metrics = rehearsal(cell, seed=11, steps=7 if serving else 3)
    assert result["correct"]
    from_device = {m["name"] for m in BENCH["per_layer"]
                   if m["source"] == "device_trace"}
    on_cpu = new - from_device
    assert on_cpu <= set(metrics), sorted(on_cpu - set(metrics))
    assert not (from_device & set(metrics)), "a device number from a CPU"
    for name in on_cpu:
        assert metrics[name]["value"] >= 0, name
    if serving:
        result["trace"] = xplane.Trace(synthetic_device(
            result, "serve.decode_step",
            "serve.paged/bucket={bucket}/chunk={chunk}"),
            result["trace"].host)
    else:
        result["trace"] = xplane.Trace(synthetic_device(
            result, "train.step", "train.step"), result["trace"].host)
    bench = dict(BENCH, per_layer=[m for m in BENCH["per_layer"]
                                   if m["name"] in new])
    again = bench_run.layer_metrics(bench, cell, result)
    assert set(again) == new, sorted(new - set(again))
    for name in new & from_device:
        assert again[name]["value"] > 0, name
