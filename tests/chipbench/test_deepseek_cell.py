"""Tests of what the DeepSeek-V2 PR adds to the benchmark, on the CPU: the
configuration file against the catalog's published keys, both traffic files
against the issue's parameters, the work of attention over a latent cache
on hand-made counts, and rehearsals of `deepseek-v2.doc-qa-closed`
(composition a function of the files, documents brought one at a time,
`correct` by the comparison with the plain reference and false with the
softmax scale's mscale left out, the traced metrics) and of
`gpt2-medium.prompt-closed`. No test claims a device number.
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
from chipbench import work, work_latent, work_mla  # noqa: E402
from test_chipbench import rehearsal  # noqa: E402,F401

CELL = "deepseek-v2.doc-qa-closed"
PROMPT_CELL = "gpt2-medium.prompt-closed"
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "deepseek-v2-serve-ep8.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "chipbench", "traffic", "doc-qa-closed.json")))
PROMPT_TRAFFIC = json.load(open(os.path.join(
    ROOT, "chipbench", "traffic", "prompt-closed.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
REDUCED = {"num_hidden_layers": (6, 60), "n_routed_experts": (20, 160),
           "vocab_size": (12800, 102400)}
SHAPES = {"layers": 6, "heads": 128, "kv_lora_rank": 512,
          "latent_width": 576, "prefill_chunk": 96, "itemsize": 2,
          "index_heads": 0, "index_dim": 0, "hidden": 5120,
          "q_lora_rank": 1536, "qk_nope_head_dim": 128, "v_head_dim": 128,
          "dense_layers": 1, "dense_width": 12288, "expert_width": 1536,
          "shared_experts": 2, "experts_per_token": 6, "experts_held": 20,
          "router_width": 160, "vocab": 12800}


def test_config_file_holds_the_published_keys_and_the_ep8_share():
    published = CONFIG["published"]
    assert published["model_type"] == "deepseek_v2"
    assert CONFIG["reduced"] == list(REDUCED) == list(CONFIG["reduced_how"])
    for key, value in published.items():
        if key in REDUCED:
            assert (CONFIG[key], value) == REDUCED[key], key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["share"] == {"router_width": 160, "first_expert": 0,
                               "vocab_slices": 8}
    assert {"prefill_chunk", "rope", "router", "weights", "sampling"} \
        <= set(CONFIG["assumed"])
    assert "8 devices share each expert layer" in CONFIG["deployment"]
    from chipbench.kinds import serve_docs
    from mxnet_tpu.models import deepseek
    cfg = serve_docs.model_config(CONFIG)
    for key, value in deepseek.DEEPSEEK_V2_PUBLISHED.items():
        if key == "n_routed_experts":
            assert (cfg[key], cfg["experts_held"]) == (160, 20)
        elif key not in REDUCED:
            assert cfg[key] == value == CONFIG[key], key
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 12800)
    assert (cfg["first_expert"], cfg["dtype"]) == (0, "bfloat16")
    assert round(deepseek.param_count(cfg) / 1e5) == 38146
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "deepseek-v2-serve-ep8")
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert CONFIG["server"] == {"page_size": 64, "slots": 32,
                                "buckets": [17408], "pool_pages": 4096,
                                "prefill_chunk": 96}


def test_traffic_file_is_the_issue_s_documents_and_cycle():
    cycle = TRAFFIC["cycle"]
    assert cycle == [
        [32, 128], [96, 384], [48, 192], [128, 512], [64, 256], [80, 320],
        [40, 160], [112, 448], [56, 224], [72, 288], [36, 144], [104, 416],
        [44, 176], [120, 480], [88, 352], [60, 240]]
    assert (sum(q for q, _ in cycle), sum(n for _, n in cycle)) \
        == (16 * 74 - 4, 16 * 295)
    docs = TRAFFIC["doc_cycle"]
    assert docs == [8192, 16384, 12288, 10240, 8192, 14336, 12288, 16384]
    assert sum(docs) == 8 * 12288 and all(d % 64 == 0 for d in docs)
    server = CONFIG["server"]
    assert TRAFFIC["clients"] == server["slots"] == 32
    assert (TRAFFIC["seats"], TRAFFIC["asks_per_document"]) == (8, 16)
    assert TRAFFIC["primer"] == [32, 8]
    longest = max(docs) + max(q + n for q, n in cycle)
    assert longest <= server["buckets"][0] == 272 * 64
    # the 8 live documents, 32 questions and answers, one document on its
    # way in: the pool holds them and keeps retired documents beside them
    live = sum(docs) // 64 + 32 * -(-max(q + n for q, n in cycle) // 64) \
        + max(docs) // 64
    assert live < server["pool_pages"]
    # the audited requests: client 0 reads document 0 from the tree, client
    # 23 is the third reader of seat 7 (14 asks counted) and brings document
    # 8; both documents are 8,192 tokens
    assert TRAFFIC["audited"] == [0, 23]
    assert 23 % 8 == 7 and 2 * 7 + 2 == 16 and docs[8 % 8] == docs[0] == 8192
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "doc-qa-closed", "deepseek-v2-serve-ep8")


def test_prompt_traffic_file_is_the_issue_s_cycle_through_bucket_1024():
    cycle = PROMPT_TRAFFIC["cycle"]
    assert cycle == [
        [128, 16], [160, 24], [192, 32], [128, 20], [256, 48], [144, 16],
        [224, 28], [384, 40], [136, 18], [176, 36], [512, 44], [152, 22],
        [208, 30], [768, 48], [168, 26], [320, 34]]
    assert (sum(p for p, _ in cycle), sum(n for _, n in cycle)) \
        == (16 * 254 - 8, 16 * 30 + 2)
    gpt = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "gpt2-medium-serve.json")))
    assert PROMPT_TRAFFIC["clients"] == gpt["server"]["slots"] == 32
    assert 256 < max(p + n for p, n in cycle) <= 1024
    cell = next(w for w in BENCH["workloads"] if w["name"] == PROMPT_CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "prompt-closed", "gpt2-medium-serve")


def test_latent_attention_work_from_the_stretch_s_counts():
    """Two traced steps. Decoding rows: 31 at a context of 12,000 each
    step. Prompt rows: one chunk of 96 at positions 6,000..6,095."""
    ctx_chunk = sum(range(6001, 6097))
    traced = {"steps": 2, "tokens": 2 * 127,
              "ctx_tokens": 2 * (31 * 12000 + ctx_chunk),
              "sel_tokens": 2 * (31 * 12000 + ctx_chunk),
              "decode_ctx_tokens": 2 * 31 * 12000, "emitted": 2 * 31,
              "row_passes": 0,
              "sel_row_passes": 2 * (31 * 12000 + ctx_chunk / 96)}
    shapes = dict(SHAPES, traced=traced)
    flops, nbytes = work_mla.paged_latent_attention(shapes)
    assert 2 * 128 * (576 + 512) == 278528
    assert flops == 6 * 278528 * (31 * 12000 + ctx_chunk)
    # the chunk's last row sees 6,096 rows; the floor counts the mean
    assert nbytes == 6 * 1152 * (31 * 12000 + ctx_chunk / 96)
    assert ctx_chunk / 96 == 6048.5 < 6096
    seconds, bound = work.least_seconds(
        flops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute"       # the chunk's rows share their bytes
    # the accepted whole-step share reads a model with no indexer: its
    # attention is this kernel's work, its bytes this kernel's
    step_flops, step_bytes = work_latent.serve_step(shapes)
    assert work_latent.sparse_attention(shapes) == (flops, nbytes)
    attention = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 \
        + 128 * 512 * 256 + 128 * 128 * 5120
    per_token = 6 * attention + 3 * 5120 * 12288 \
        + 5 * (5120 * 160 + 3 * 5120 * 1536 * (2 + 6 * 20 / 160))
    assert step_flops == 2 * (per_token * 127 + 5120 * 12800 * 31) + flops
    assert step_bytes == nbytes


def test_new_entries_are_appended_and_name_readers_that_exist():
    new = ["serve.paged_latent_attention_ms_per_step",
           "serve.paged_latent_roofline"]
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == new
    assert [w["name"] for w in BENCH["workloads"][-2:]] == [CELL, PROMPT_CELL]
    assert BENCH["configs"][-1]["name"] == "deepseek-v2-serve-ep8"
    assert len(BENCH["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    for m in BENCH["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "token_gap_p95_ms"
        spec = json.load(open(os.path.join(
            ROOT, "chipbench", "layer_metrics", m["name"] + ".json")))
        assert spec["args"]["match"] == ["paged_latent_attention"]
        assert (spec["unit"], spec["layer"]) == (m["unit"], m["layer"])
    listed = {m["name"] for m in BENCH["per_layer"]
              if bench_run.applies(m, CELL)}
    assert set(new) | {
        "serve.step_mfu", "serve.latent_attention_ms_per_step",
        "serve.moe_ms_per_step", "serve.kv_arena_ms_per_step",
        "serve.prefix_hit_share", "serve.token_step_ms_p50",
        "serve.chunk_step_ms_p50", "setup.compile_s"} <= listed
    # no indexer, no head axis in the cache, no window
    assert not listed & {"serve.sparse_index_ms_per_step",
                         "serve.sparse_attention_roofline",
                         "serve.paged_attention_ms_per_step",
                         "serve.gqa_step_mfu"}
    prompt = {m["name"] for m in BENCH["per_layer"]
              if bench_run.applies(m, PROMPT_CELL)}
    assert {"serve.paged_attention_ms_per_step", "serve.chunk_step_ms_p50",
            "serve.kv_arena_ms_per_step", "setup.compile_s"} <= prompt
    assert not prompt & {"serve.sched_step_ms_p50",
                         "serve.prefill_step_share"}
    for m in BENCH["end_to_end"]:
        if m["name"].startswith(("serve_", "token_", "ttft_")):
            assert m["workloads"][-2:] == [CELL, PROMPT_CELL]


def test_docs_cell_rehearses_correct_whatever_the_seed(
        rehearsal, capsys):  # noqa: F811
    short_a = rehearsal(CELL, seed=7, steps=30)
    short_b = rehearsal(CELL, seed=2 ** 31 + 12345, steps=30)
    longer = rehearsal(CELL, seed=7, steps=44)
    said = capsys.readouterr().out
    assert said.count("audit request") == 6 and "NOT CORRECT" not in said
    for res in (short_a, short_b, longer):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert short_a["composition"] == short_b["composition"]
    assert longer["composition"][:30] == short_a["composition"]
    assert short_a["counters"] == short_b["counters"]
    assert short_a["documents"] == short_b["documents"]
    # documents arrive one at a time, by serial, each brought by one
    # request; its seat's other readers open it only after that
    docs = longer["documents"]
    brought = [serial for _, serial, brings in docs if brings]
    assert brought == list(range(2, 2 + len(brought))) and len(brought) >= 3
    for k, (client, serial, brings) in enumerate(docs):
        if serial >= 2 and not brings:
            first = next(j for j, d in enumerate(docs) if d[1] == serial)
            assert first < k and docs[first][2]
            assert docs[first][0] % 2 == client % 2     # the same seat
    counters = short_a["counters"]
    # many prompt tokens come from the tree, and not all: documents are
    # brought inside the window
    assert 0.3 < counters["prefix_tokens"] / counters["prompt_tokens"] < 1


def test_docs_cell_fails_with_mscale_left_out(rehearsal,  # noqa: F811
                                              monkeypatch):
    """The comparison that decides `correct` is not an invariant check: a
    served model whose softmax scale lacks YaRN's mscale^2 runs, finishes
    every request, and is NOT correct."""
    from mxnet_tpu.models import deepseek
    real = deepseek.rope_and_scale

    def plain_scale(cfg):
        inv, on_rope, _ = real(cfg)
        return inv, on_rope, (cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"]) ** -0.5

    monkeypatch.setattr(deepseek, "rope_and_scale", plain_scale)
    res = rehearsal(CELL, seed=3, steps=20)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert not res["correct"]


def test_docs_cell_reads_its_traced_metrics(rehearsal):  # noqa: F811
    """The traced stretch of the rehearsal: every per-layer metric of the
    cell that needs no device reads a number, the executables hold the
    scopes the scope metrics read, and the work functions find their
    counts."""
    res = rehearsal(CELL, seed=5, steps=12, trace=True)
    assert res["correct"] and res["traced_steps"] == 4
    traced = res["shapes"]["traced"]
    assert traced["steps"] == 4 and traced["tokens"] > 0
    assert traced["ctx_tokens"] == traced["sel_tokens"] \
        >= traced["decode_ctx_tokens"] > 0
    res["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = work_mla.paged_latent_attention(res["shapes"])
    assert flops > 0 and nbytes > 0
    metrics = bench_run.layer_metrics(BENCH, CELL, res)
    for name in ("serve.prefix_hit_share", "serve.batch_occupancy",
                 "serve.pool_pages_in_use_share", "serve.step_mfu",
                 "setup.initialize_s", "setup.compile_s"):
        assert metrics[name]["value"] > 0, name
    from mxnet_tpu import trace
    for chunk in (1, 6):
        label = f"serve.paged/bucket=64/chunk={chunk}"
        paths = trace.scope_map(label)[label].values()
        for scope in ("kv_arena_update", "latent_attention", "moe_experts",
                      "lm_head"):
            assert any(f"/{scope}/" in p for p in paths), (chunk, scope)


def test_prompt_cell_rehearses_whatever_the_seed(rehearsal):  # noqa: F811
    short_a = rehearsal(PROMPT_CELL, seed=7, steps=40)
    short_b = rehearsal(PROMPT_CELL, seed=2 ** 31 + 12345, steps=40)
    for res in (short_a, short_b):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert short_a["composition"] == short_b["composition"]
    assert short_a["counters"] == short_b["counters"]
    # prompts several chunks long: most steps hold a request inside its
    # prompt, and the requests need the larger bucket
    counters = short_a["counters"]
    assert counters["prefill_steps"] > counters["steps"] // 2
