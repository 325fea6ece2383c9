"""Tests of what the Laguna cell adds to the benchmark, on the CPU: the
configuration file against the catalog's published keys, the traffic file
against the issue's cycle, the work of grouped-query attention over two
page classes on hand-made counts, and a rehearsal of
`laguna-xs2.mixed-length-closed` (composition a function of the files,
`correct` by the comparison with the plain reference and false with the
window ignored, the traced metrics). No test claims a device number.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench import work, work_window  # noqa: E402
from test_chipbench import rehearsal  # noqa: E402,F401

CELL = "laguna-xs2.mixed-length-closed"
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "laguna-xs2-serve-pp8.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "chipbench", "traffic", "mixed-length-closed.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SHAPES = {"layers": 5, "full_layer_heads": [48, 48],
          "window_layer_heads": [64, 64, 64], "kv_heads": 8, "head_dim": 128,
          "window": 512, "prefill_chunk": 32, "itemsize": 2, "hidden": 2048,
          "dense_layers": 1, "dense_width": 8192, "expert_width": 512,
          "shared_width": 512, "experts_per_token": 8, "experts": 256,
          "vocab": 100352}


def test_config_file_holds_the_published_keys_and_cuts_depth_only():
    published = CONFIG["published"]
    assert published["model_type"] == "laguna"
    assert CONFIG["reduced"] == ["num_hidden_layers"] \
        == list(CONFIG["reduced_how"])
    for key, value in published.items():
        if key == "num_hidden_layers":
            assert (CONFIG[key], value) == (5, 40)
        else:
            assert CONFIG[key] == value, key
    n = CONFIG["num_hidden_layers"]
    # a whole period after the leading dense layer, every kind present
    assert CONFIG["layer_types"][:n] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert CONFIG["num_attention_heads_per_layer"][:n] == [48, 64, 64, 64, 48]
    assert CONFIG["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert {"gating", "router", "attention", "weights"} \
        <= set(CONFIG["assumed"])
    assert "8 pipeline stages of 5 layers" in CONFIG["deployment"]
    from chipbench.kinds import serve_mixed
    from mxnet_tpu.models import laguna
    cfg = serve_mixed.model_config(CONFIG)
    for key in laguna.LAGUNA_XS2_PUBLISHED:
        assert cfg[key] == CONFIG[key], key
    assert cfg["dtype"] == "bfloat16"
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "laguna-xs2-serve-pp8")
    assert entry["source"] == CONFIG["source"] \
        == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert entry["reduced"] == CONFIG["reduced"]


def test_traffic_file_is_the_issue_s_cycle_through_one_bucket():
    cycle = TRAFFIC["cycle"]
    assert cycle == [
        [128, 256], [4096, 256], [512, 128], [256, 384], [1024, 192],
        [8192, 384], [192, 512], [768, 160], [384, 320], [6144, 192],
        [96, 448], [640, 224], [160, 288], [12288, 320], [896, 128],
        [320, 352]]
    assert (sum(p for p, _ in cycle), sum(n for _, n in cycle)) \
        == (36096, 4544)
    server = CONFIG["server"]
    assert TRAFFIC["clients"] == server["slots"] == 32
    assert server["buckets"] == [12800] and server["page_size"] == 64
    assert max(p + n for p, n in cycle) <= server["buckets"][0]
    assert server["pool_pages"] == 32 * 12800 // 64
    # the audited requests: one long, one short, both of the opening burst
    long, short = (cycle[k] for k in TRAFFIC["audited"])
    assert long == [8192, 384] and short == [128, 256]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "mixed-length-closed")


def test_paged_attention_work_from_the_stretch_s_counts():
    """Two traced steps. Decoding rows: 20 at a context of 1,000 each step.
    Prompt rows: one chunk of 32 at positions 4,000..4,031 each step."""
    ctx_chunk = sum(range(4001, 4033))
    traced = {"steps": 2, "tokens": 2 * 52,
              "ctx_tokens": 2 * (20 * 1000 + ctx_chunk),
              "window_tokens": 2 * (20 * 512 + 32 * 512),
              "decode_ctx_tokens": 2 * 20 * 1000,
              "decode_window_tokens": 2 * 20 * 512, "emitted": 2 * 20}
    shapes = dict(SHAPES, traced=traced)
    flops, nbytes = work_window.paged_attention(shapes)
    assert flops == 4 * 128 * (96 * (20 * 1000 + ctx_chunk)
                               + 192 * 52 * 512)
    # the chunk's last row sees 4,032 keys; the floor counts the mean, 4,016.5
    assert nbytes == 4096 * (2 * (20 * 1000 + ctx_chunk / 32)
                             + 3 * (20 * 512 + 512))
    assert ctx_chunk / 32 == 4016.5 < 4032
    seconds, bound = work.least_seconds(
        flops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "bandwidth"
    step_flops, step_bytes = work_window.serve_step(shapes)
    per_token = 2 * (2 * 2048 * 128 * 48 + 2048 * 48) \
        + 3 * (2 * 2048 * 128 * 64 + 2048 * 64) + 5 * 2 * 2048 * 8 * 128 \
        + 3 * 2048 * 8192 \
        + 4 * (2048 * 256 + 3 * 2048 * (512 + 8 * 512))
    assert step_flops == 2 * (per_token * 52 + 2048 * 100352 * 20) + flops
    assert step_bytes == nbytes


def test_new_metrics_name_readers_that_exist_and_list_the_cell():
    new = {"serve.full_attention_ms_per_step",
           "serve.window_attention_ms_per_step", "serve.paged_gqa_roofline",
           "serve.window_cache_share", "serve.gqa_step_mfu"}
    listed = {m["name"] for m in BENCH["per_layer"]
              if bench_run.applies(m, CELL)}
    assert new <= listed
    assert {m["name"] for m in BENCH["per_layer"][-5:]} == new
    assert BENCH["workloads"][-1]["name"] == CELL
    # a step of this cell is never all narrow passes: the one serving
    # metric that reads such steps alone does not list the cell
    assert "serve.token_step_ms_p50" not in listed
    for name in ("serve.paged_attention_ms_per_step", "serve.moe_ms_per_step",
                 "serve.kv_arena_ms_per_step", "serve.chunk_step_ms_p50"):
        assert name in listed


def test_mixed_cell_rehearses_correct_whatever_the_seed(
        rehearsal, capsys):  # noqa: F811
    short_a = rehearsal(CELL, seed=7, steps=24)
    short_b = rehearsal(CELL, seed=2 ** 31 + 12345, steps=24)
    longer = rehearsal(CELL, seed=7, steps=36)
    said = capsys.readouterr().out
    assert said.count("audit request") == 6 and "NOT CORRECT" not in said
    for res in (short_a, short_b, longer):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert short_a["composition"] == short_b["composition"]
    assert longer["composition"][:24] == short_a["composition"]
    assert short_a["counters"] == short_b["counters"]
    counters = short_a["counters"]
    # prompts of up to 40 tokens under a window of 12: the window class
    # holds a fraction of what the same requests hold in the full class
    assert 0 < counters["window_class_pages_sum"] \
        < 0.6 * counters["full_class_pages_sum"]
    assert counters["full_class_pages_sum"] == counters["pages_in_use_sum"]


def test_mixed_cell_fails_with_the_window_ignored(rehearsal,  # noqa: F811
                                                  monkeypatch):
    """The comparison that decides `correct` is not an invariant check: a
    served model whose sliding layers attend everything (over pages the
    pool has taken back) runs, finishes every request, and is NOT
    correct."""
    from mxnet_tpu import pallas_ops
    real = pallas_ops.paged_attention

    def everything(q, k_pages, v_pages, tables, t, window=None):
        return real(q, k_pages, v_pages, tables, t, None)

    monkeypatch.setattr(pallas_ops, "paged_attention", everything)
    res = rehearsal(CELL, seed=3, steps=20)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert not res["correct"]


def test_mixed_cell_reads_its_traced_metrics(rehearsal):  # noqa: F811
    """The traced stretch of the rehearsal: every per-layer metric of the
    cell that needs no device reads a number, the executables hold the
    scopes the scope metrics read, and the work functions find their
    counts."""
    res = rehearsal(CELL, seed=5, steps=12, trace=True)
    assert res["correct"] and res["traced_steps"] == 4
    traced = res["shapes"]["traced"]
    assert traced["steps"] == 4 and traced["tokens"] > 0
    assert traced["ctx_tokens"] > traced["window_tokens"] > 0
    assert traced["ctx_tokens"] >= traced["decode_ctx_tokens"]
    assert traced["window_tokens"] >= traced["decode_window_tokens"]
    res["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = work_window.paged_attention(res["shapes"])
    assert flops > 0 and nbytes > 0
    metrics = bench_run.layer_metrics(BENCH, CELL, res)
    for name in ("serve.window_cache_share", "serve.batch_occupancy",
                 "serve.pool_pages_in_use_share", "serve.gqa_step_mfu",
                 "setup.initialize_s", "setup.compile_s"):
        assert metrics[name]["value"] > 0, name
    assert metrics["serve.window_cache_share"]["value"] < 100
    from mxnet_tpu import trace
    for chunk in (1, 2):
        label = f"serve.paged/bucket=64/chunk={chunk}"
        paths = trace.scope_map(label)[label].values()
        for scope in ("kv_arena_update", "full_attention",
                      "window_attention", "moe_experts", "lm_head"):
            assert any(f"/{scope}/" in p for p in paths), (chunk, scope)
