"""The ladder of pass widths (PR 38): a step's tokens go through the layer
stack in the fewest passes a ladder of widths allows, and a pass runs the
narrowest rung that holds its rows.

(a) the rungs for (slots, prefill_chunk) pairs: `slots` and `2 * slots`
    always among them, doublings while a rung stays within 256 rows and
    under `slots * prefill_chunk`, the most a step can owe;
(b) `_passes` cuts a step's feeds at the top rung, `_pass` takes the
    narrowest rung that holds its rows;
(c) the served ids are `model.generate`'s (GPT-2, alone and under a
    drafter), a request's own when served alone, and those of a server
    held to the two narrowest rungs, on steps that mix decoding rows with
    several prompts: the GPT-2, GLM-5, Laguna (a window class) and
    DeepSeek-V2 test models;
(d) a warmed bucket holds one executable a rung, each built once: the
    admission's probe adds no build, and nothing is built after the step
    of the admission;
(e) every rung has a `chunk` of its own on the spans, and
    `mx.trace.scope_map` knows the executable it names;
(f) what makes a build cheap enough for a third and fourth rung: GPT-2's
    stack of layers is traced and lowered once (`_decode.layer_call`), with
    the logits of a loop over the layers."""
import functools
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import config, memsafe, parallel, serve, trace
from mxnet_tpu.models import deepseek as deepseek_mod
from mxnet_tpu.models import glm as glm_mod
from mxnet_tpu.models import gpt as gpt_mod
from mxnet_tpu.models import laguna as laguna_mod

_VOCAB = 96


@pytest.fixture(autouse=True)
def _clean():
    from mxnet_tpu.parallel import mesh as mesh_mod
    before = mesh_mod._current["mesh"]
    yield
    serve.disable()
    trace.disable()
    trace.reset()
    config.reset()
    mesh_mod.set_mesh(before)


@functools.lru_cache(maxsize=None)      # weights are read, never written
def _model(family):
    parallel.make_mesh(devices=jax.devices()[:1])
    model = {
        "gpt": lambda: gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config()),
        "glm": lambda: glm_mod.GLMForCausalLM(glm_mod.glm_tiny_config()),
        "laguna": lambda: laguna_mod.LagunaForCausalLM(
            laguna_mod.laguna_tiny_config()),
        "deepseek": lambda: deepseek_mod.DeepseekForCausalLM(
            deepseek_mod.deepseek_tiny_config()),
    }[family]()
    mx.random.seed(3)
    model.initialize()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, _VOCAB, (n,)) \
        .astype(np.int32)


def _server(family, **kw):
    model = _model(family)
    parallel.make_mesh(devices=jax.devices()[:1])
    args = dict(slots=4, page_size=4, prefill_chunk=16, buckets=[64])
    args.update(kw)
    return serve.Server(model, **args)


def _two_rungs(monkeypatch):
    """Hold the servers built from here on to the parent's two widths:
    with the ridge under them the ladder ends at `2 * slots`."""
    monkeypatch.setattr(serve, "_RIDGE_ROWS", 0)


# -- (a) the rungs ------------------------------------------------------------

@pytest.mark.parametrize("slots,chunk,rungs", [
    (32, 8, (32, 64, 128)),             # the GPT-2 and GLM-5 cells
    (32, 32, (32, 64, 128, 256)),       # Laguna's
    (32, 96, (32, 64, 128, 256)),       # DeepSeek-V2's
    (32, 1, (32,)),                     # no step owes more than `slots`
    (32, 2, (32, 64)),
    (32, 3, (32, 64)),
    (32, 5, (32, 64, 128)),
    (4, 8, (4, 8, 16)),                 # the package's defaults
    (4, 4, (4, 8)),
    (2, 4, (2, 4)),
    (2, 8, (2, 4, 8)),
    (8, 3, (8, 16)),
    (16, 96, (16, 32, 64, 128, 256)),
    (64, 8, (64, 128, 256)),
    (128, 8, (128, 256)),
    (100, 4, (100, 200)),               # 400 rows are past the ridge
    (512, 8, (512, 1024)),              # the parent's two, past the ridge
])
def test_the_rungs(slots, chunk, rungs):
    got = serve.Server._ladder(slots, chunk)
    assert got == rungs
    assert got[0] == slots and (chunk == 1 or got[1] == 2 * slots)
    assert all(b == 2 * a for a, b in zip(got, got[1:]))
    most = slots * chunk
    # past the parent's two, a rung stays within the ridge and under the
    # most a step can owe, and the next doubling would not
    assert all(w <= serve._RIDGE_ROWS and w < most for w in got[2:])
    assert chunk == 1 or 2 * got[-1] > serve._RIDGE_ROWS \
        or 2 * got[-1] >= most


def test_the_ridge_is_the_chips():
    """256: the next power of two over the rows at which a bf16 weight's
    read and its use take the same time on a TPU v5e, from the package's
    own table of peaks."""
    from mxnet_tpu import inspect as mx_inspect
    flops = dict(mx_inspect._PEAK_FLOPS_TABLE)["v5e"]
    bytes_s = dict(mx_inspect._PEAK_BW_TABLE)["v5e"]
    rows = flops / bytes_s          # 2 operations a row and 2-byte weight
    assert 128 < rows <= serve._RIDGE_ROWS == 256


@pytest.mark.parametrize("slots,chunk", [(4, 16), (2, 8), (4, 1), (3, 5)])
def test_a_server_names_its_rungs(slots, chunk):
    srv = _server("gpt", slots=slots, prefill_chunk=chunk)
    rungs = serve.Server._ladder(slots, chunk)
    assert srv._rungs == rungs and srv._wide() == rungs[-1]
    chunks = [srv._chunk_of(w) for w in rungs]
    # a name: 1 for `slots`, `prefill_chunk` for `2 * slots`, one value a
    # rung, all others above 1
    assert chunks[0] == 1 and len(set(chunks)) == len(chunks)
    assert chunk == 1 or chunks[1] == chunk
    assert all(c > 1 for c in chunks[1:])
    assert srv._chunk_of(slots * 3, full=True) == srv._spec_k + 1
    srv.stop()


# -- (b) the fewest passes, the narrowest rung --------------------------------

@pytest.mark.parametrize("owed", [1, 4, 5, 16, 31, 32, 33, 64, 70])
def test_passes_are_cut_at_the_top_rung(owed):
    srv = _server("gpt")                # rungs 4, 8, 16, 32
    feeds, left, slot = [], owed, 0
    while left:
        n = min(left, 9)
        feeds.append((slot % 4, 10 * slot, list(range(n))))
        left, slot = left - n, slot + 1
    passes = srv._passes(feeds)
    sizes = [sum(len(ids) for _, _, ids in rows) for rows in passes]
    assert len(passes) == -(-owed // 32), "the fewest the ladder allows"
    assert all(n == 32 for n in sizes[:-1]) and 0 < sizes[-1] <= 32
    # the same tokens at the same positions, in order
    flat = [(i, p + k, t) for rows in passes for i, p, ids in rows
            for k, t in enumerate(ids)]
    assert flat == [(i, p + k, t) for i, p, ids in feeds
                    for k, t in enumerate(ids)]
    srv.stop()


def test_a_pass_takes_the_narrowest_rung_that_holds_it():
    trace.enable()
    srv = _server("gpt")
    gpt = _model("gpt")
    prompts = [_prompt(n, n) for n in (3, 21, 18, 2, 26, 5, 12)]
    ref = [gpt.generate(p[None], max_new_tokens=6, on_device=False)[0]
           .tolist() for p in prompts]
    reqs = [srv.submit(p, max_new_tokens=6) for p in prompts]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert [list(r.tokens) for r in reqs] == ref
    rounds = [s for s in trace.spans() if s["name"] == "serve.decode_step"]
    assert len(rounds) == st["steps"] > 0
    for s in rounds:
        assert s["width"] == min(w for w in srv._rungs if w >= s["fed"])
    by_step = {}
    for s in rounds:
        by_step.setdefault(s["step"], []).append(s["fed"])
    # a step takes a second pass only when the first is a full top rung
    assert all(fed[:-1] == [32] * (len(fed) - 1) for fed in by_step.values())
    assert set(st["width_dispatches"]) == set(srv._rungs), \
        "the mix ran every rung"
    assert st["rows_dispatched"] == sum(
        w * n for w, n in st["width_dispatches"].items())
    assert st["rows_fed"] == st["attn_tokens"] == sum(
        s["fed"] for s in rounds)


# -- (c) the same ids ----------------------------------------------------------

# (prompt, new): decoding rows beside several prompts inside their chunks
_MIX = [(5, 12), (37, 6), (21, 9), (3, 14), (44, 5), (13, 8), (29, 7)]


def _serve_mix(family, **kw):
    srv = _server(family, **kw)
    reqs = [srv.submit(_prompt(n, 7 + n), max_new_tokens=m)
            for n, m in _MIX]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert all(r.state == serve.DONE for r in reqs), reqs
    return srv, st, [list(r.tokens) for r in reqs]


@pytest.mark.parametrize("family", ["gpt", "glm", "laguna", "deepseek"])
def test_ids_are_the_two_rung_servers(monkeypatch, family):
    srv, st, ids = _serve_mix(family)
    assert srv._rungs == (4, 8, 16, 32)
    assert st["executables"] == 4
    assert sum(n for w, n in st["width_dispatches"].items() if w > 8) > 0, \
        "the mix must run the rungs the parent lacks"
    _two_rungs(monkeypatch)
    srv2, st2, ids2 = _serve_mix(family)
    assert srv2._rungs == (4, 8) and st2["executables"] == 2
    assert ids == ids2
    # the same tokens fed, in fewer passes
    assert st["rows_fed"] == st2["rows_fed"]
    assert st["steps"] < st2["steps"]
    assert st["scheduler_steps"] == st2["scheduler_steps"]


def test_ids_are_generates():
    gpt = _model("gpt")
    _, _, ids = _serve_mix("gpt")
    for (n, m), got in zip(_MIX, ids):
        want = gpt.generate(_prompt(n, 7 + n)[None], max_new_tokens=m,
                            on_device=False)[0].tolist()
        assert got == want


@pytest.mark.parametrize("family", ["glm", "laguna", "deepseek"])
def test_ids_under_load_are_the_ids_alone(family):
    """The models without a `generate` of their own: a request's tokens in
    the mix are its tokens when it is served alone (a row's logits never
    depend on its neighbours, whatever rung carries them)."""
    _, _, ids = _serve_mix(family)
    srv = _server(family)
    for k in (1, 4):                    # a long prompt, the longest
        n, m = _MIX[k]
        alone = srv.submit(_prompt(n, 7 + n), max_new_tokens=m)
        srv.drain()
        assert list(alone.tokens) == ids[k]
    srv.stop()


def test_ids_under_a_drafter(monkeypatch):
    gpt = _model("gpt")
    srv, st, ids = _serve_mix("gpt", drafter=gpt, spec_k=2)
    # the rungs, the drafter's mirror of each, the verify pass, the chain
    assert st["executables"] == 2 * len(srv._rungs) + 2 == 10
    assert st["spec_rounds"] > 0
    for (n, m), got in zip(_MIX, ids):
        want = gpt.generate(_prompt(n, 7 + n)[None], max_new_tokens=m,
                            on_device=False)[0].tolist()
        assert got == want
    _two_rungs(monkeypatch)
    _, st2, ids2 = _serve_mix("gpt", drafter=gpt, spec_k=2)
    assert ids2 == ids and st2["executables"] == 6


# -- (d) one executable a rung, each built once --------------------------------

class _Builds:
    """Counts jax's lowerings and backend compiles while it is entered."""
    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, _secs, **_kw):
        key = self.EVENTS.get(event)
        if key and self.on:
            self.n[key] += 1

    def __enter__(self):
        self.n = {"lowered": 0, "compiled": 0}
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False


_builds = functools.cache(_Builds)      # jax keeps a listener for good: one


def _first_step_builds(probe, **kw):
    """(lowerings, compiles) of the step that admits a bucket's first
    request, with the admission's probe armed (a device capacity is
    known) or not."""
    if probe:
        config.set("device_bytes_limit", 1 << 40)
    srv = _server("gpt", **kw)
    srv.submit(_prompt(3, 1), max_new_tokens=2)
    with _builds() as builds:
        srv.step()
    assert srv.stats()["executables"] == len(srv._rungs)
    assert (srv._exec_peaks.get(64) is not None) == probe
    config.reset()
    return srv, builds.n


def test_the_probe_adds_no_build(monkeypatch):
    _first_step_builds(False)           # the helpers' jits, once a process
    srv, plain = _first_step_builds(False)
    _, probed = _first_step_builds(True)
    assert probed == plain, "the probe reads the top rung's own build"
    _two_rungs(monkeypatch)
    srv2, two = _first_step_builds(True)
    extra = len(srv._rungs) - len(srv2._rungs)
    assert extra == 2
    # a rung is one lowering and one compile, the probe's included
    assert probed["lowered"] - two["lowered"] == extra
    assert probed["compiled"] - two["compiled"] == extra


@pytest.mark.parametrize("probe", [False, True], ids=["plain", "probed"])
def test_nothing_is_built_after_the_admission(probe):
    srv, _ = _first_step_builds(probe)
    built = dict(srv._built[64])
    assert sorted(w for w, _, _ in built) == sorted(srv._rungs)
    if probe:
        # the budget read the heaviest of the builds it will run
        assert srv._exec_peaks[64] == max(
            memsafe.compiled_exec_peak(c) for c in built.values())
    reqs = [srv.submit(_prompt(n, n), max_new_tokens=5) for n in (30, 19, 7)]
    before = trace.setup()["compile_s"]
    with _builds() as builds:
        srv.drain()
    st = srv.stats()
    srv.stop()
    assert all(r.state == serve.DONE for r in reqs)
    assert len(st["width_dispatches"]) > 2, "several rungs ran"
    assert builds.n == {"lowered": 0, "compiled": 0}
    assert st["executables"] == len(srv._rungs)
    assert trace.setup()["compile_s"] == before
    assert srv._built[64] == built


def test_compile_s_holds_the_builds():
    before = trace.setup()["compile_s"]
    srv, _ = _first_step_builds(True)
    assert trace.setup()["compile_s"] > before
    srv.stop()


def test_a_refused_build_is_heard_at_warm_up(monkeypatch):
    """A compiler that refuses a rung: the probe degrades to the resident
    check, nothing is kept as built, and the step that seats the request
    hears the refusal as an admission refusal."""
    config.set("device_bytes_limit", 1 << 40)
    srv = _server("gpt")
    real = srv._step_avals

    def refusing(bucket, width, tag="target"):
        if width == 16:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real(bucket, width, tag)

    monkeypatch.setattr(srv, "_step_avals", refusing)
    req = srv.submit(_prompt(3, 1), max_new_tokens=2)
    srv.step()
    assert srv._exec_peaks[64] is None and 64 not in srv._built
    assert req.state == serve.REJECTED and "429" in req.verdict
    assert 64 in srv._unfit and 64 not in srv._warmed
    srv.stop()


# -- (e) names -------------------------------------------------------------------

def test_every_rung_has_a_name_scope_map_knows():
    label = "serve.paged/bucket={bucket}/chunk={chunk}"
    trace.enable()
    srv = _server("gpt")
    for n, m in _MIX:
        srv.submit(_prompt(n, 7 + n), max_new_tokens=m)
    srv.drain()
    st = srv.stats()
    srv.stop()
    spans = trace.spans()
    rounds = [s for s in spans if s["name"] == "serve.decode_step"]
    chunk_of = {w: srv._chunk_of(w) for w in srv._rungs}
    assert chunk_of == {4: 1, 8: 16, 16: 32, 32: 64}
    assert {s["width"] for s in rounds} == set(srv._rungs)
    for s in rounds:
        assert s["chunk"] == chunk_of[s["width"]]
    for width, chunk in chunk_of.items():
        names = trace.scope_map(label.format(bucket=64, chunk=chunk))
        assert len(names) == 1
        paths = next(iter(names.values())).values()
        assert any("/kv_arena_update/" in path for path in paths), width
    steps = {s["step"]: s for s in spans if s["name"] == "serve.step"}
    for step, s in steps.items():
        assert s["chunk"] == max(
            r["chunk"] for r in rounds if r["step"] == step)
    assert sum(s["chunk"] > 1 for s in rounds) == st["chunk_steps"] > 0
    assert sum(s["chunk"] == 1 for s in rounds) == st["token_steps"] > 0


# -- (f) a stack of layers traced once -------------------------------------------

def test_the_layers_are_one_function_in_the_step():
    srv = _server("gpt")
    n_layers = len(_model("gpt").gpt.layers)
    text = srv.lower_step(64).as_text()
    srv.stop()
    assert n_layers > 1
    assert len(re.findall(r"func\.func private @layer\(", text)) == 1
    assert len(re.findall(r"\bcall @layer\(", text)) == n_layers


def test_layer_call_gives_the_logits_of_a_loop_over_the_layers(monkeypatch):
    def mix():
        srv = _server("gpt")
        reqs = [srv.submit(_prompt(n, 7 + n), max_new_tokens=m,
                           keep_logits=True) for n, m in _MIX[:4]]
        srv.drain()
        srv.stop()
        return [np.stack(r.logits) for r in reqs]

    shared = mix()
    monkeypatch.setattr(
        gpt_mod, "layer_call",
        lambda layers, i, method, *args: getattr(layers[i], method)(*args))
    looped = mix()
    for a, b in zip(shared, looped):
        np.testing.assert_array_equal(a, b)


def test_layer_call_leaves_the_parameters_as_they_were():
    model = _model("gpt")
    layers = list(model.gpt.layers)
    before = [p.data()._data for _, p in layers[0]._iter_params()]
    _serve_mix("gpt")
    after = [p.data()._data for _, p in layers[0]._iter_params()]
    assert all(a is b for a, b in zip(before, after))
