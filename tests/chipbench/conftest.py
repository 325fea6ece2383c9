"""A test module written when its cell was the benchmark's newest pins the
tail of `BENCHMARK.json` ("my entries are the last"). The benchmark only
ever grows at the end, and a file that is here is not edited, so such a
module is shown the benchmark as it was when its entries were appended: the
lists cut after its own last entry. What it then checks still holds of the
file as it is: those entries are there, whole, in the order they were
added."""
import pytest

# module -> (its last cell, its last per-layer metric)
TAIL_WHEN_WRITTEN = {
    "test_laguna_cell": ("laguna-xs2.mixed-length-closed",
                         "serve.gqa_step_mfu"),
}


def _through(entries, name):
    names = [e["name"] for e in entries]
    return entries[:names.index(name) + 1]


@pytest.fixture(autouse=True)
def _benchmark_as_the_module_left_it(request, monkeypatch):
    tail = TAIL_WHEN_WRITTEN.get(request.module.__name__)
    if tail is not None:
        bench = request.module.BENCH
        monkeypatch.setattr(request.module, "BENCH", dict(
            bench, workloads=_through(bench["workloads"], tail[0]),
            per_layer=_through(bench["per_layer"], tail[1])))
