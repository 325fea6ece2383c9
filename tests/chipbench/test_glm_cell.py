"""Tests of what the GLM-5 cell adds to the benchmark, on the CPU: the
configuration file against the catalog's published keys, the work of
sparse attention over a latent cache and the scope-based roofline reader
on hand-made inputs, and a rehearsal of `glm-5.agent-prefix-closed`
(composition a function of the files, `correct` by the comparison with
the plain reference, prefix hits). No test claims a device number.
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
from chipbench import work, work_latent  # noqa: E402
from chipbench.readers import trace_scope_roofline  # noqa: E402
from test_chipbench import rehearsal  # noqa: E402,F401
from test_program_spans import hand_built, program  # noqa: E402,F401

CELL = "glm-5.agent-prefix-closed"
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "glm-5-serve-ep16.json")))
SHAPES = {"layers": 6, "heads": 64, "kv_lora_rank": 512, "latent_width": 576,
          "index_heads": 32, "index_dim": 128, "index_topk": 2048,
          "itemsize": 2}


def test_config_file_holds_the_published_keys_and_says_what_it_cut():
    published, reduced = CONFIG["published"], CONFIG["reduced"]
    assert published["model_type"] == "glm_moe_dsa"
    for key, value in published.items():
        if key in reduced:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert set(reduced) == set(CONFIG["reduced_how"])
    # no width is cut; the floors: 4 expert layers, 8 experts, 1/8 vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["share"]["router_width"] == published["n_routed_experts"]
    from chipbench.kinds import serve_agent
    from mxnet_tpu.models import glm
    cfg = serve_agent.model_config(CONFIG)
    for key in glm.GLM5_PUBLISHED:
        if key not in ("rope_theta", "n_routed_experts"):
            assert cfg[key] == CONFIG[key], key
    assert (cfg["n_routed_experts"], cfg["experts_held"]) == (256, 16)
    assert glm.param_count(cfg) * 2 / 1e9 == pytest.approx(9.45, abs=0.01)
    traffic = json.load(open(os.path.join(
        ROOT, "chipbench", "traffic", "agent-prefix-closed.json")))
    longest = traffic["prefix_tokens"] + max(s + n for s, n
                                             in traffic["cycle"])
    assert longest == CONFIG["server"]["buckets"][0]
    assert traffic["prefix_tokens"] % CONFIG["server"]["page_size"] == 0


def test_sparse_attention_work_from_the_stretch_s_counts():
    """(e) Two traced steps: 32 rows fed one token each at a context of
    5,000 (3,000 + 2,000 own rows cached... the counts are given), then
    the same again."""
    traced = {"steps": 2, "ctx_tokens": 2 * 32 * 5000,
              "sel_tokens": 2 * 32 * 2048, "row_passes": 2 * 20000,
              "sel_row_passes": 2 * 2048}
    flops, nbytes = work_latent.sparse_attention(dict(SHAPES, traced=traced))
    assert flops == 6 * 32 * (2 * 32 * 128 * 5000 + 2 * 64 * 1088 * 2048)
    assert nbytes == 6 * 2 * (128 * 20000 + 576 * 2048)
    # under the context the selection keeps, attention reads every row
    short = {"steps": 1, "ctx_tokens": 100, "sel_tokens": 100,
             "row_passes": 100, "sel_row_passes": 100}
    flops, nbytes = work_latent.sparse_attention(dict(SHAPES, traced=short))
    assert flops == 6 * 100 * (2 * 32 * 128 + 2 * 64 * 1088)
    assert nbytes == 6 * 2 * 100 * (128 + 576)
    seconds, bound = work.least_seconds(
        *work_latent.sparse_attention(dict(SHAPES, traced=traced)),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute"


def test_scope_roofline_reader_on_a_hand_built_stretch(program):  # noqa: F811
    """(e) The share divides the least time for the step's work by the
    device self time under the scopes, executables told apart by span:
    `copy.5` is under `kv_arena_update` in the chunk executable only (3 of
    4 steps, 2 ms each), `paged_attention.3` under `page_gather` (1 ms)."""
    result, spans = hand_built()
    program(spans)
    result["peaks"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    result["shapes"] = dict(SHAPES, traced={
        "steps": 4, "ctx_tokens": 0, "sel_tokens": 0,
        "row_passes": 4 * 1e6, "sel_row_passes": 0})
    args = {"label": "serve.paged/bucket={bucket}/chunk={chunk}",
            "outer": "serve.step", "per_span": "serve.decode_step",
            "work_fn": "work_latent.sparse_attention"}
    # bytes a step: 6 layers x 2 B x 128 x 1e6 rows = 1.536e9 -> 1.536 ms
    share = trace_scope_roofline.read(
        result, scopes=["kv_arena_update", "page_gather"], **args)
    assert share == pytest.approx(100 * 1.536 / (3 * 3.0 / 4))
    assert trace_scope_roofline.read(result, scopes=["nosuch"], **args) \
        is None
    # the parent of the PR that adds the scopes: no map, nothing to read
    program(spans, scopes={})
    assert trace_scope_roofline.read(
        result, scopes=["kv_arena_update"], **args) is None
    del result["shapes"]["traced"]
    program(spans)
    assert trace_scope_roofline.read(
        result, scopes=["kv_arena_update"], **args) is None


def test_agent_cell_rehearses_correct_with_the_prefix_from_the_tree(
        rehearsal, capsys):  # noqa: F811
    """(f) Composition equal across two seeds and a prefix of a longer
    run; `correct` by the reference at float32; every prompt's prefix
    from the tree."""
    short_a = rehearsal(CELL, seed=7, steps=30)
    short_b = rehearsal(CELL, seed=2 ** 31 + 12345, steps=30)
    longer = rehearsal(CELL, seed=7, steps=45)
    said = capsys.readouterr().out
    assert said.count("audit request") == 6 and "NOT CORRECT" not in said
    for res in (short_a, short_b, longer):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert len(short_a["composition"]) == 30
    assert short_a["composition"] == short_b["composition"]
    assert longer["composition"][:30] == short_a["composition"]
    assert short_a["counters"] == short_b["counters"]
    counters = short_a["counters"]
    assert counters["prefix_tokens"] > 0
    # 16 of each prompt's 21-30 tokens come from the tree
    assert 0.5 < counters["prefix_tokens"] / counters["prompt_tokens"] < 0.8
    assert counters["sparse_tokens"] == counters["attn_tokens"] > 0
    assert 0 < counters["prefill_steps"] < counters["steps"]


def test_agent_cell_fails_on_a_selection_of_fewer_tokens(rehearsal,
                                                         monkeypatch):
    """The comparison that decides `correct` is not an invariant check: a
    served model that keeps half the published `index_topk` runs, finishes
    every request, and is NOT correct."""
    from mxnet_tpu.models import glm
    real = glm.GLMForCausalLM.__init__

    def fewer(self, cfg, **kw):
        real(self, dict(cfg, index_topk=cfg["index_topk"] // 2), **kw)

    monkeypatch.setattr(glm.GLMForCausalLM, "__init__", fewer)
    res = rehearsal(CELL, seed=3, steps=20)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert not res["correct"]


def test_agent_cell_reads_its_traced_metrics(rehearsal):
    """The traced stretch of the rehearsal: every per-layer metric of the
    cell that needs no device reads a number, the scope metrics read the
    scopes of the chunk and token executables, and the roofline's work
    is there for the reader."""
    res = rehearsal(CELL, seed=5, steps=12, trace=True)
    assert res["correct"] and res["traced_steps"] == 2
    traced = res["shapes"]["traced"]
    assert traced["steps"] == 2 and traced["tokens"] > 0
    assert traced["sel_tokens"] == 8 * traced["tokens"] \
        == 8 * traced["sparse_tokens"]
    assert traced["ctx_tokens"] > traced["sel_tokens"]
    assert traced["row_passes"] >= traced["sel_row_passes"] > 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    res["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    metrics = bench_run.layer_metrics(bench, CELL, res)
    for name in ("serve.prefix_hit_share", "serve.batch_occupancy",
                 "serve.pool_pages_in_use_share", "setup.initialize_s",
                 "setup.compile_s"):
        assert metrics[name]["value"] > 0, name
    from mxnet_tpu import trace
    for chunk in (1, 4):
        paths = trace.scope_map(f"serve.paged/bucket=64/chunk={chunk}")[
            f"serve.paged/bucket=64/chunk={chunk}"].values()
        for scope in ("kv_arena_update", "sparse_index", "latent_attention",
                      "moe_experts", "lm_head"):
            assert any(f"/{scope}/" in p for p in paths), (chunk, scope)
