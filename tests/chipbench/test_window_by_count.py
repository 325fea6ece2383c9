"""Tests of the serving cells' protocol by counts (chipbench/window.py,
kinds serve and serve_agent), on the CPU: the clock only measures. Two runs
of a cell with different seeds and different luck with the clock hold the
same window; the traced stretch sits at the traffic file's step and holds
both kinds of step; the floor under the sparse-attention roofline counts
one pass a dispatch; a window that would take over twice `--seconds` fails
the run. No test claims a device number.
"""
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench import window, work_latent  # noqa: E402
from chipbench.kinds import serve, serve_agent  # noqa: E402
from test_chipbench import BENCH  # noqa: E402

SERVING = [w["name"] for w in BENCH["workloads"]
           if "serve_tokens_per_s" in
           [m["name"] for m in BENCH["end_to_end"]
            if bench_run.applies(m, w["name"])]]
SECONDS = 3.0       # 180 tokens at the rehearsal's 60 a second


@pytest.fixture
def run_cell(monkeypatch):
    """Run a serving cell's driver in this process as `--rehearsal` does,
    its window closed by the protocol and not by a count of steps. `stall`
    makes every step of the loop that much slower."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    before = mesh_mod._current["mesh"]
    plain_step = serve.ClosedLoop.step

    def go(cell, seed, seconds=SECONDS, trace=False, stall=0.0):
        def slow_step(self):
            time.sleep(stall)
            plain_step(self)

        monkeypatch.setattr(serve.ClosedLoop, "step",
                            slow_step if stall else plain_step)
        _, ctx = bench_run.context(cell, seed, seconds, trace, True)
        return bench_run.driver_of(ctx).run(ctx)

    yield go
    mesh_mod.set_mesh(before)


class FakeLoop:
    """A loop of made-up steps for `window.measure`: step k emits `yields[k
    % len(yields)]` units of work and takes `step_s` seconds."""

    def __init__(self, yields, step_s=0.0, ready_after=0):
        self.yields, self.step_s, self.ready_after = yields, step_s, \
            ready_after
        self.work = self.steps = 0

    def step(self):
        time.sleep(self.step_s)
        self.work += self.yields[self.steps % len(self.yields)]
        self.steps += 1

    def count(self, target, trace_from=0):
        return window.ByCount(lambda: self.work, target,
                              lambda: self.steps >= self.ready_after,
                              trace_from)


def fake_ctx(seconds, trace=False, steps=None):
    return types.SimpleNamespace(seconds=seconds, trace=trace, steps=steps,
                                 keep_trace=None,
                                 t_start=time.perf_counter())


@pytest.mark.parametrize("step_s", [0.0, 0.004])
def test_a_counted_window_closes_on_the_work_whatever_the_pace(step_s):
    """10 units of work: steps yield 3, 1, 4, ... from the window's
    opening, so it closes after the fourth step (3 + 1 + 4 + 3), fast or
    slow; the warm-up runs its 2 steps and then on until `ready`."""
    loop = FakeLoop([3, 1, 4], step_s, ready_after=5)
    win = window.measure(fake_ctx(5.0), loop.step, lambda: None, 2, 0,
                         loop.count(target=10))
    # the window opens at step 5: yields 4, 3, 1, 4 reach 10 in four steps
    assert (win.warmup_steps, win.steps, win.overran) == (5, 4, False)
    assert loop.steps == 9 and win.traced_steps == 0
    assert win.t1 - win.t0 >= 4 * step_s


def test_a_counted_window_over_twice_its_seconds_is_closed_and_marked():
    loop = FakeLoop([0], step_s=0.02)       # no work is ever done
    win = window.measure(fake_ctx(0.05), loop.step, lambda: None, 1, 0,
                         loop.count(target=1))
    assert win.overran and 0.1 < win.t1 - win.t0 < 1.0
    # without a count the clock closes the window, and nothing overruns
    loop = FakeLoop([0], step_s=0.02)
    win = window.measure(fake_ctx(0.05), loop.step, lambda: None, 1, 0)
    assert not win.overran and 0.05 <= win.t1 - win.t0 < 0.5


def test_a_traced_window_ends_where_the_stretch_has_to_begin():
    loop = FakeLoop([1])
    win = window.measure(fake_ctx(5.0, trace=True), loop.step, lambda: None,
                         3, 4, loop.count(target=10 ** 6, trace_from=11))
    assert win.warmup_steps + win.steps == 11 and win.traced_steps == 4
    assert loop.steps == 15 and not win.overran
    # the tests' count of steps still closes any window
    loop = FakeLoop([1])
    win = window.measure(fake_ctx(5.0, steps=6), loop.step, lambda: None,
                         3, 0, loop.count(target=10 ** 6, trace_from=11))
    assert (win.warmup_steps, win.steps) == (3, 6)


@pytest.mark.parametrize("cell", SERVING)
def test_two_runs_hold_the_same_window_whatever_the_seed_and_the_clock(
        run_cell, cell):
    """Different seeds, one run with every step stalled by 3 ms: the same
    steps, tokens, first tokens, gaps, wide steps and whole-window hash.
    `--seconds` scales the window."""
    plain = run_cell(cell, seed=7)
    stalled = run_cell(cell, seed=2 ** 31 + 12345, stall=0.003)
    shorter = run_cell(cell, seed=7, seconds=SECONDS / 2)
    for res in (plain, stalled, shorter):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert plain["window"] == stalled["window"]
    assert plain["composition"] == stalled["composition"]
    assert plain["counters"] == stalled["counters"]
    target = 60 * SECONDS
    assert target <= plain["window"]["tokens"] < target + 8
    assert plain["window"]["gaps"] > 0 < plain["window"]["first_tokens"]
    assert 0 < plain["window"]["wide_steps"] < plain["window"]["steps"]
    assert target / 2 <= shorter["window"]["tokens"] < target / 2 + 8
    assert shorter["composition"] \
        == plain["composition"][:shorter["window"]["steps"]]
    assert stalled["end_to_end"]["serve_tokens_per_s"] \
        < plain["end_to_end"]["serve_tokens_per_s"]


@pytest.mark.parametrize("cell", SERVING)
def test_the_traced_stretch_sits_at_the_file_s_step_and_holds_both_kinds(
        run_cell, cell):
    _, _, _, traffic = bench_run.cell_files(cell, rehearsal=True)
    res = run_cell(cell, seed=5, trace=True)
    held = res["window"]
    assert res["correct"] and res["traced_steps"] == traffic["trace_steps"]
    assert held["stretch"]["from_step"] == traffic["trace_from_step"] \
        == held["warmup_steps"] + held["steps"]
    assert held["stretch"]["wide_steps"] >= 1
    assert held["stretch"]["token_steps"] >= 1
    assert held["stretch"]["wide_steps"] + held["stretch"]["token_steps"] \
        == traffic["trace_steps"]
    # wherever --seconds would have closed the window, the stretch is there
    again = run_cell(cell, seed=6, seconds=SECONDS / 3, trace=True)
    assert again["window"] == held


def test_the_warm_up_runs_on_until_the_opening_burst_has_its_first_tokens(
        run_cell):
    """The agent cell's rehearsal needs a step more than its file's three:
    no first token inside the window is one of the opening four's."""
    cell = "glm-5.agent-prefix-closed"
    _, _, _, traffic = bench_run.cell_files(cell, rehearsal=True)
    res = run_cell(cell, seed=9)
    assert res["window"]["warmup_steps"] == traffic["warmup_steps"] + 1
    loop = serve.ClosedLoop.__new__(serve.ClosedLoop)
    loop.opening = 2
    loop.requests = [types.SimpleNamespace(stamps=[1.0]),
                     types.SimpleNamespace(stamps=[]),
                     types.SimpleNamespace(stamps=[])]
    assert not loop.opening_has_first_tokens()
    loop.requests[1].stamps.append(2.0)     # the third is not of the burst
    assert loop.opening_has_first_tokens()


@pytest.mark.parametrize("cell", SERVING)
def test_a_window_that_would_take_over_twice_its_seconds_fails_the_run(
        run_cell, cell):
    res = run_cell(cell, seed=3, seconds=0.25, stall=0.2)
    assert res["window"]["tokens"] < 60 * 0.25
    assert not res["correct"] and res["failed"] >= 1
    assert res["checks"]["window_overran"] == [1, 0]


def test_the_roofline_s_floor_counts_one_pass_a_dispatch():
    """Three traced steps by hand: a wide pass over 100 cached rows, a
    token pass over 3,000, and a step of two passes (one of each) over
    5,000, with `index_topk` 2,048. Four passes, not 8 + 1 + 9."""
    fed = [{"chunk_steps": 1, "token_steps": 0, "attn_tokens": 40,
            "attn_ctx_tokens": 4000, "attn_sel_tokens": 4000,
            "sparse_tokens": 40},
           {"chunk_steps": 0, "token_steps": 1, "attn_tokens": 32,
            "attn_ctx_tokens": 96000, "attn_sel_tokens": 65536,
            "sparse_tokens": 32},
           {"chunk_steps": 1, "token_steps": 1, "attn_tokens": 70,
            "attn_ctx_tokens": 350000, "attn_sel_tokens": 143360,
            "sparse_tokens": 70}]
    traced = serve_agent.stretch_counts(fed, [100, 3000, 5000], 2048, 97)
    assert traced["steps"] == 3 and traced["passes"] == 4
    assert traced["tokens"] == traced["sparse_tokens"] == 142
    assert traced["row_passes"] == 100 + 3000 + 2 * 5000
    assert traced["sel_row_passes"] == 100 + 2048 + 2 * 2048
    shapes = {"layers": 6, "heads": 64, "kv_lora_rank": 512,
              "latent_width": 576, "index_heads": 32, "index_dim": 128,
              "index_topk": 2048, "itemsize": 2, "traced": traced}
    _, nbytes = work_latent.sparse_attention(shapes)
    assert nbytes == 6 * 2 * (128 * 13100 + 576 * 6244) / 3


def test_step_mfu_reads_the_whole_step_s_share_of_the_peak():
    """By hand: one layer of width 4, one head, two tokens a sequence. The
    layer's products are 2 x 2 x (4 x 16 + 2 x 4 x 8) + 2 x 2 x 2 x 2 x 4
    = 576 multiply-adds, the head's 2 x 1 x (16 + 4 x 10) = 112; three
    passes (forward, backward twice), two operations each: 4,128 a step. At
    1,000 operations a second and 8 s a step that is 51.6 % of the peak."""
    from chipbench import work
    from chipbench.readers import step_mfu
    shapes = {"batch_per_chip": 2, "seq_len": 2, "units": 4, "hidden": 8,
              "heads": 1, "head_dim": 4, "layers": 1, "masked": 1,
              "vocab": 10}
    assert work.encoder_train_step(shapes) == (6 * (576 + 112), 0)
    result = {"shapes": shapes, "traced_steps": 2, "traced_window_s": 16.0,
              "peaks": {"bf16_flops_per_s": 1000.0}}
    assert step_mfu.read(result, "work.encoder_train_step") \
        == pytest.approx(51.6)
    assert step_mfu.read(dict(result, traced_steps=0),
                         "work.encoder_train_step") is None
    # shapes of another kind of cell: nothing to read, not a zero
    assert step_mfu.read(result, "work_latent.serve_step") is None


def test_serve_step_work_counts_every_layer_s_products_once_a_token():
    """GLM-5's share at its published widths: 165.0 M multiply-adds of
    attention and 9.4 M of indexer a token and layer (PERF.md's cut), one
    dense layer of 226.5 M, five expert layers of a router, one shared
    expert and half a routed one (8 of 256 picks find 16 held)."""
    shapes = {"layers": 6, "heads": 64, "kv_lora_rank": 512,
              "latent_width": 576, "index_heads": 32, "index_dim": 128,
              "index_topk": 2048, "itemsize": 2, "hidden": 6144,
              "q_lora_rank": 2048, "qk_nope_head_dim": 192,
              "v_head_dim": 256, "dense_layers": 1, "dense_width": 12288,
              "expert_width": 2048, "shared_experts": 1,
              "experts_per_token": 8, "experts_held": 16,
              "router_width": 256, "vocab": 19360,
              "traced": {"steps": 2, "tokens": 64, "emitted": 60,
                         "ctx_tokens": 0, "sel_tokens": 0, "row_passes": 0,
                         "sel_row_passes": 0}}
    attention = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 \
        + 64 * 512 * 448 + 64 * 256 * 6144
    assert round(attention / 1e6, 1) == 165.0
    indexer = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    expert = 6144 * 256 + 3 * 6144 * 2048 * 1.5
    per_token = 6 * (attention + indexer) + 3 * 6144 * 12288 + 5 * expert
    flops, nbytes = work_latent.serve_step(shapes)
    assert flops == pytest.approx(
        2 * (per_token * 64 + 6144 * 19360 * 60) / 2)
    assert nbytes == 0


def test_the_agent_cell_s_stretch_counts_its_dispatches(run_cell):
    """The rehearsal's stretch holds a wide step (`prefill_chunk` 4): its
    passes are its dispatches, one a step here, not four for the wide."""
    res = run_cell("glm-5.agent-prefix-closed", seed=5, trace=True)
    traced, stretch = res["shapes"]["traced"], res["window"]["stretch"]
    assert stretch["wide_steps"] >= 1
    assert traced["steps"] <= traced["passes"] <= traced["steps"] + 1
    assert traced["row_passes"] >= traced["sel_row_passes"] > 0


@pytest.mark.parametrize("cell", SERVING)
def test_a_serving_traffic_file_states_its_protocol(cell):
    """What `gpt2-medium.prompt-closed` will have to state too: the keys
    the count-closed window and the placed stretch read, at the real size
    and at the rehearsal's; a window of `run_seconds` is some hundreds of
    steps before the stretch's place, so a traced run measures too."""
    traffic = json.load(open(os.path.join(
        ROOT, "chipbench", "traffic",
        next(w["traffic"] for w in BENCH["workloads"]
             if w["name"] == cell) + ".json")))
    for group in (traffic, traffic["rehearsal"]):
        for key in ("warmup_steps", "window_tokens_per_s",
                    "trace_from_step", "trace_steps"):
            assert isinstance(group[key], int) and group[key] > 0, key
        assert group["trace_from_step"] > group["warmup_steps"]
    assert traffic["trace_steps"] >= 8
    assert traffic["trace_from_step"] >= 500
