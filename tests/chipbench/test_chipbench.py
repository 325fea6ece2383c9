"""Tests of the on-chip benchmark's harness (chipbench/), on the CPU.

They check the files against the contract's limits, the arithmetic from
samples and traces to metrics on made-up inputs, and the one property the
serving cell stands on: the work of scheduler step k is a function of the
cell's files alone, whatever the seed. No test describes a TPU topology or
claims a device number.
"""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench import stats, work, xplane  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def metrics_of(cell, group):
    return [m for m in BENCH[group] if bench_run.applies(m, cell)]


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    named = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
        + BENCH["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}, m
        assert m["source"] in ("host_clock", "device_trace"), m
        assert 0.01 <= m["bound"] <= 0.1, m
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
    for text in [w["why"] for w in BENCH["workloads"] + BENCH["configs"]] \
            + [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text, text
    # 2 + 14 runs a cell at the full 24 cells must fit the check's time
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_exists(cell):
    _, entry, config, traffic = bench_run.cell_files(cell)
    assert entry["chips"] == traffic["chips"] and entry["chips"] in (1, 4)
    cfg_entry = next(c for c in BENCH["configs"]
                     if c["name"] == entry["config"])
    assert cfg_entry["file"].startswith(tuple(BENCH["paths"]))
    assert config["reduced"] == cfg_entry["reduced"]
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "kinds", config["kind"] + ".py"))
    for m in metrics_of(cell, "per_layer"):
        spec = json.load(open(os.path.join(
            ROOT, "chipbench", "layer_metrics", m["name"] + ".json")))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "readers", spec["reader"] + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_moved_metric_is_reported_where_its_mover_is(cell):
    end_to_end = {m["name"] for m in metrics_of(cell, "end_to_end")}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    per_layer = metrics_of(cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in end_to_end, (cell, m["name"], m["moves"])


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


TRACE = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[], bf16[8,4]{1,0}) while(%tuple.3), condition=%cond" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_attention.3 = bf16[32,16,64]{2,1,0} custom-call(%a, %b)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.602 = bf16[2080,16,16,64]{3,1,2,0} copy(%p)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.7 = f32[8]{0} fusion(%x), kind=kLoop" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 50000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 70000000 duration_ps: 30000000 }
    events { metadata_id: 4 offset_ps: 100002000 duration_ps: 9998000 }
    events { metadata_id: 4 offset_ps: 150000000 duration_ps: 50000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 999000000 }
  }
}
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.refill" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
  lines { id: 7 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 120000000 }
    events { metadata_id: 2 offset_ps: 120000000 duration_ps: 60000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 500000000 }
  }
}
"""


def hand_built_trace():
    from jax.profiler import ProfileData
    return xplane.load(ProfileData.text_proto_to_serialized_xspace(TRACE))


def test_xplane_busy_union_gaps_and_time_by_name():
    trace = hand_built_trace()
    assert list(trace.devices) == ["/device:TPU:0"]
    assert [e[0] for e in trace.host] == ["bench.step", "bench.refill"]
    # busy: [0, 100us] + [100.002, 110] + [150, 200] = 159.998 us
    assert xplane.busy_seconds(trace) == pytest.approx(159.998e-6)
    by_name = xplane.seconds_by_name(trace)
    assert by_name["paged_attention.3"] == pytest.approx(60e-6)
    assert by_name["copy.602"] == pytest.approx(20e-6)
    assert by_name["while.1"] == pytest.approx(20e-6)   # self time only
    assert by_name["fusion.7"] == pytest.approx(59.998e-6)
    assert xplane.seconds_matching(trace, ["paged_att", "copy."]) \
        == pytest.approx(80e-6)
    gaps = xplane.idle_gaps(trace)
    assert gaps[xplane.SMALL_GAPS] == pytest.approx(2e-9)
    assert gaps["bench.refill"] == pytest.approx(40e-6)  # middle at 130us
    assert xplane.top(xplane.grouped(by_name), 2) == [
        ["paged_attention", pytest.approx(60e-6)],
        ["fusion.7", pytest.approx(59.998e-6)]]
    assert xplane.base_name("transpose_jvp_flash_dkv__.14") \
        == "transpose_jvp_flash_dkv__"


def test_readers_read_and_return_nothing_when_nothing_is_there():
    from chipbench.readers import (counter_ratio, span_stat,
                                   trace_busy_ms_per_step,
                                   trace_op_ms_per_step, trace_roofline)
    result = {"spans": {"bench.step": [0.1, 0.3, 0.2]},
              "counters": {"prefill_steps": 8, "steps": 10},
              "trace": hand_built_trace(), "traced_steps": 2,
              "shapes": {"batch_per_chip": 1, "heads": 1, "seq_len": 128,
                         "head_dim": 64, "layers": 1, "itemsize": 2},
              "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}}
    assert span_stat.read(result, "bench.step", "p50") == pytest.approx(200)
    assert span_stat.read(result, "bench.step", "mean") == pytest.approx(200)
    assert span_stat.read(result, "bench.input", "mean") is None
    assert counter_ratio.read(result, "prefill_steps", "steps") == 80.0
    assert counter_ratio.read(result, "absent", "steps") is None
    assert trace_op_ms_per_step.read(result, ["paged_attention"]) \
        == pytest.approx(0.03)
    assert trace_op_ms_per_step.read(result, ["flash_fwd"]) is None
    assert trace_busy_ms_per_step.read(result) == pytest.approx(0.079999)
    # 7 matmuls of 2*128*128*64 flops at 1 TF/s = 14.68 us, over 30 us
    share = trace_roofline.read(result, ["paged_attention"],
                                "work.flash_attention_train")
    assert share == pytest.approx(100 * 7 * 2 * 128 * 128 * 64 / 1e12 / 30e-6)
    empty = dict(result, trace=None, traced_steps=0)
    assert trace_busy_ms_per_step.read(empty) is None
    assert trace_roofline.read(empty, ["x"], "work.flash_attention_train") is None


def test_gap_ttft_percentile_and_spread_arithmetic():
    assert stats.token_gaps([1.0, 1.5, 2.5, 2.75]) == [0.5, 1.0, 0.25]
    assert stats.token_gaps([3.0]) == []
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([], 95) is None
    # two requests: 0.8 s for 16 prompt tokens, 2.4 s for 64
    assert stats.ttft_ms_per_prompt_token([0.8, 2.4], [16, 64]) \
        == pytest.approx(40.0)
    assert stats.ttft_ms_per_prompt_token([], []) is None
    assert stats.spread([100, 101, 102, 103, 104, 105]) \
        == pytest.approx(3.5 / 102.5)
    rows = [(4, 0, 4), (0, 1, 3), (1, 0, 4)]
    assert stats.composition_hash(rows) == stats.composition_hash(
        [list(r) for r in rows])
    assert stats.composition_hash(rows) != stats.composition_hash(rows[:2])


def test_flash_work_is_seven_matmuls_a_layer():
    shapes = {"batch_per_chip": 32, "heads": 12, "seq_len": 512,
              "head_dim": 64, "layers": 12, "itemsize": 2}
    flops, nbytes = work.flash_attention_train(shapes)
    assert flops == 12 * 7 * 2 * 32 * 12 * 512 * 512 * 64
    assert nbytes == 12 * 12 * 32 * 12 * 512 * 64 * 2
    seconds, bound = work.least_seconds(
        flops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and seconds == pytest.approx(flops / 197e12)


@pytest.fixture
def rehearsal(monkeypatch):
    """Run a cell's driver in this process as `--rehearsal` does: the CPU
    devices the test session has, Pallas through the interpreter, tiny
    sizes. The mesh the driver installs is put back afterwards."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    before = mesh_mod._current["mesh"]

    def go(cell, seed, steps, trace=False):
        _, ctx = bench_run.context(cell, seed, 0.0, trace, True, steps=steps)
        return bench_run.driver_of(ctx).run(ctx)

    yield go
    mesh_mod.set_mesh(before)


def test_serving_work_is_a_function_of_the_files_not_the_seed(rehearsal):
    cell = "gpt2-medium.decode-closed"
    short_a = rehearsal(cell, seed=7, steps=40)
    short_b = rehearsal(cell, seed=2 ** 31 + 12345, steps=40)
    longer = rehearsal(cell, seed=7, steps=60)
    for res in (short_a, short_b, longer):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert len(short_a["composition"]) == 40
    assert short_a["composition"] == short_b["composition"]
    assert longer["composition"][:40] == short_a["composition"]
    assert short_a["counters"] == short_b["counters"]
    # the loop is closed: every finished request is replaced at once
    slots = short_a["shapes"]["slots"]
    assert all(running <= slots for _, _, running in short_a["composition"])
    assert sum(a for a, _, _ in short_a["composition"]) > 0
    assert 0 < short_a["counters"]["prefill_steps"] \
        < short_a["counters"]["steps"]


def test_serving_driver_has_no_rate_thread_sleep_or_length_draw():
    code = open(os.path.join(ROOT, "chipbench", "kinds", "serve.py")).read()
    for banned in ("sleep(", "Thread(", ".start()", "poisson", "uniform(",
                   "choice("):
        assert banned not in code, banned
    assert code.count("randint(") == 1      # the token ids, nothing else


@pytest.mark.parametrize("cell", ["bert-base.pretrain-1chip",
                                  "bert-base.pretrain-dp4"])
def test_training_driver_counts_whole_steps(rehearsal, cell):
    res = rehearsal(cell, seed=3, steps=4)
    assert res["failed"] == 0               # every loss finite
    assert res["attempted"] == 4 + 3            # window + warm-up
    assert len(res["spans"]["bench.input"]) == 4
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["shapes"]["head_dim"] == 16


def test_run_py_exits_non_zero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_TPU_PALLAS_INTERPRET", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0
    assert "not 'tpu'" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
