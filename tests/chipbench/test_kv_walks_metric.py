"""`serve.kv_walks_per_step` (PR 40): the step table's `walks` over the
window, through a reader that leaves the metric out where the program's
table has no such field (a parent commit's), and raises nothing."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.readers import program_step_field, program_step_table  # noqa

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _rows(with_walks):
    row = {"step": 0, "total_s": 0.01, "wide": 1, "passes": 1, "fed": 250,
           "schedule_s": 0.0, "prepare_s": 0.0, "dispatch_s": 0.0,
           "fetch_s": 0.0, "stream_s": 0.0, "admitted": 0, "compiles": 0,
           "compile_s": 0.0, "polls": 1, "poll_gap_max_s": 0.0}
    rows = [dict(row, step=k) for k in range(4)]
    if with_walks:
        for k, r in enumerate(rows):
            r["walks"] = 30 + k
    return rows


def test_reads_the_mean_walks_and_leaves_out_a_table_without_them(
        monkeypatch):
    for with_walks, want in ((True, 31.5), (False, None)):
        monkeypatch.setattr(program_step_table, "window_rows",
                            lambda result, w=with_walks: _rows(w))
        assert program_step_field.read({}, stat="mean",
                                       field="walks") == want
    monkeypatch.setattr(program_step_table, "window_rows",
                        lambda result: None)
    assert program_step_field.read({}, stat="mean", field="walks") is None


def test_the_metric_names_its_reader_and_the_kernel_s_cells():
    spec = json.load(open(os.path.join(
        ROOT, "chipbench", "layer_metrics", "serve.kv_walks_per_step.json")))
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "serve.kv_walks_per_step")
    assert spec["reader"] == "program_step_field"
    assert spec["args"] == {"stat": "mean", "field": "walks"}
    kernel = next(m for m in BENCH["per_layer"]
                  if m["name"] == "serve.paged_attention_ms_per_step")
    assert entry["layer"] == kernel["layer"] == spec["layer"]
    assert entry["workloads"] == kernel["workloads"]
    assert entry["moves"] == spec["moves"] == "serve_tokens_per_s"
