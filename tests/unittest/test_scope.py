"""mx.scope tests: the scope=off zero-thread/zero-call fast path, every
endpoint's payload over real HTTP, torn-read-free /metrics scrapes under
concurrent registry mutation (the PR 4 atomic-dumps guarantee extended
to the HTTP path), on-demand /profilez device capture (409 on
concurrency, bit-identical loss trajectory with scope on vs off), the
in-process gang aggregator (stale/unreachable naming, a wedged rank
never wedging the fan-out), scope_top rendering, and the 2-rank launch
smokes (both ranks scraped live, aggregator gang view, gang-wide
profilez, hang@step acceptance)."""
import importlib.util
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import weakref

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, diagnostics, nd, parallel
from mxnet_tpu import profiler as mxprofiler
from mxnet_tpu import scope, serve, telemetry, trace
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAUNCH = os.path.join(ROOT, "tools", "launch.py")
SCOPE_TOP = os.path.join(ROOT, "tools", "scope_top.py")


def _load_launch():
    spec = importlib.util.spec_from_file_location("_launch_for_scope",
                                                  LAUNCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_scope(monkeypatch):
    # /statusz and /tracez read the live-server registry and the span
    # buffer: start each test from an empty one, whatever an earlier test
    # of this worker left uncollected (a stopped Server stays registered
    # until it is garbage) or recorded
    monkeypatch.setattr(serve, "_servers", weakref.WeakSet())
    trace.reset()
    yield
    scope.reset()
    telemetry.disable()
    telemetry.reset()
    diagnostics.disable()
    diagnostics.reset()
    config.reset()


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        ct = r.headers.get("Content-Type", "")
        body = r.read()
        return r.status, ct, body


def _get_json(url, timeout=10.0):
    status, _ct, body = _get(url, timeout=timeout)
    return status, json.loads(body)


def _trainer(seed=0):
    parallel.make_mesh(dp=-1)
    mx.random.seed(seed)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    lfn = gloss.L2Loss()
    return parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                                   {"learning_rate": 0.1})


def _xy():
    return (nd.array(np.ones((8, 8), np.float32)),
            nd.array(np.zeros((8, 4), np.float32)))


def _free_port_block(n=3):
    """A base port with n+1 consecutive free ports after it (aggregator
    layouts need base..base+n)."""
    for _ in range(50):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        base = s.getsockname()[1]
        s.close()
        ok = True
        for off in range(1, n + 1):
            probe = socket.socket()
            try:
                probe.bind(("127.0.0.1", base + off))
            except OSError:
                ok = False
            finally:
                probe.close()
            if not ok:
                break
        if ok:
            return base
    raise RuntimeError("no consecutive free port block found")


# ---------------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------------

def test_disabled_fast_path_no_thread_no_calls():
    assert not scope.enabled()
    assert scope._state is None and scope._server is None
    calls = {"on_step": 0}
    real = scope.on_step
    scope.on_step = lambda *a, **k: (
        calls.__setitem__("on_step", calls["on_step"] + 1), real(*a, **k))[1]
    try:
        tr = _trainer()
        x, y = _xy()
        for _ in range(3):
            tr.step(x, y)
    finally:
        scope.on_step = real
    assert calls == {"on_step": 0}
    assert scope._state is None and scope._server is None
    assert scope.port() is None and scope.url() is None
    assert not any(t.name == "mx-scope-server"
                   for t in threading.enumerate())


def test_maybe_enable_arms_from_knob():
    config.set("scope", "on")
    config.set("scope_port", 0)      # ephemeral: tests must not collide
    try:
        tr = _trainer()
        assert scope.enabled() and scope.port()
        x, y = _xy()
        tr.step(x, y)
        status, h = _get_json(scope.url() + "/healthz")
        assert status == 200 and h["step"] == 1
    finally:
        scope.disable()


def test_maybe_enable_survives_taken_port():
    """Knob-driven arming must never kill the training run it observes:
    a taken scope_port warns and stays on the zero-alloc fast path (an
    explicit enable() still raises)."""
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    taken = blocker.getsockname()[1]
    config.set("scope", "on")
    config.set("scope_port", taken)
    try:
        tr = _trainer()                # must not raise
        assert not scope.enabled()
        assert scope._state is None and scope._server is None
        x, y = _xy()
        tr.step(x, y)                  # hot path unaffected
        with pytest.raises(OSError):
            scope.enable(port=taken)
    finally:
        blocker.close()


# ---------------------------------------------------------------------------
# endpoints
# ---------------------------------------------------------------------------

def test_endpoints_serve_live_state():
    telemetry.enable()
    diagnostics.enable()
    scope.enable(port=0)
    tr = _trainer()
    x, y = _xy()
    for _ in range(4):
        tr.step(x, y)
    base = scope.url()

    status, h = _get_json(base + "/healthz")
    assert status == 200
    assert h["ok"] is True and h["rank"] == 0 and h["pid"] == os.getpid()
    assert h["step"] == 4 and h["last_step_age_s"] >= 0
    assert h["generation"] == 0

    status, ct, body = _get(base + "/metrics")
    assert status == 200 and ct.startswith("text/plain")
    text = body.decode()
    assert "trainer_step_seconds_count" in text
    assert "# TYPE trainer_step_seconds histogram" in text

    status, s = _get_json(base + "/statusz")
    assert status == 200
    assert s["step"] == 4
    assert "steps_per_s" in s
    assert s["rungs"] == {"grad_accum": 1, "zero": False,
                          "param_mode": "replicate",
                          "remat_policy": "none"}
    assert [r["step"] for r in s["ring_tail"]
            if r.get("kind") == "step"] == [1, 2, 3, 4]
    assert s["telemetry_enabled"] is True
    assert s["serve"] is None and s["profile"] is None

    status, t = _get_json(base + "/tracez")
    assert status == 200 and t["rank"] == 0 and t["spans"] == []
    # n<=0 means "no spans", never the whole buffer (spans[-0:] trap)
    status, t0 = _get_json(base + "/tracez?n=0")
    assert status == 200 and t0["spans"] == []
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base + "/tracez?n=abc")       # malformed query: 400 not 500
    assert e.value.code == 400

    status, idx = _get_json(base + "/")
    assert status == 200 and "/statusz" in idx["endpoints"]

    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base + "/nosuch")
    assert e.value.code == 404


def test_statusz_serve_section_reads_live_servers():
    scope.enable(port=0)
    scope._state.note_step(None, 7)

    class _Stub:
        def stats(self):
            return {"running": 2, "queued": 1, "completed": 9}

    stub = _Stub()
    serve._servers.add(stub)
    try:
        _status, s = _get_json(scope.url() + "/statusz")
        assert s["serve"]["servers"] == [
            {"running": 2, "queued": 1, "completed": 9}]
    finally:
        serve._servers.discard(stub)


def test_second_enable_is_idempotent():
    p1 = scope.enable(port=0)
    p2 = scope.enable(port=0)
    assert p1 == p2
    assert sum(t.name == "mx-scope-server"
               for t in threading.enumerate()) == 1


# ---------------------------------------------------------------------------
# torn-read-free /metrics under concurrent mutation (satellite)
# ---------------------------------------------------------------------------

_BUCKET_RE = re.compile(r'^(\w+)_bucket\{(.*)\} (\d+)$')
_COUNT_RE = re.compile(r'^(\w+)_count(\{[^}]*\})? (\d+(?:\.\d+)?)$')


def _parse_histograms(text):
    """buckets: {(name, labels-without-le): [(le, cum), ...]} in render
    order; counts: {(name, labels): n}. The renderer always appends the
    le label last, so stripping it is a suffix cut."""
    buckets, counts = {}, {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        m = _BUCKET_RE.match(line)
        if m:
            name, labels, n = m.group(1), m.group(2), int(m.group(3))
            parts = [p for p in labels.split(",")
                     if not p.startswith("le=")]
            le = next(p for p in labels.split(",")
                      if p.startswith("le="))[4:].strip('"')
            key = (name, "{" + ",".join(parts) + "}" if parts else "")
            buckets.setdefault(key, []).append((le, n))
            continue
        m = _COUNT_RE.match(line)
        if m:
            counts[(m.group(1), m.group(2) or "")] = int(float(m.group(3)))
    return buckets, counts


def test_metrics_scrape_never_torn_under_mutation():
    """Hammer Histogram.observe (+ label churn) from writer threads
    while scraping /metrics over HTTP: every scrape must parse with
    non-decreasing cumulative buckets whose +Inf equals _count — a torn
    bucket set would violate one of the two. The CI static stage re-runs
    this under MXNET_TPU_CHECK_THREADS=1 (tsan-lite) so the lock
    discipline behind the guarantee is itself checked."""
    telemetry.enable()
    scope.enable(port=0)
    h = telemetry.histogram("scope_torn_probe_seconds")
    c = telemetry.counter("scope_torn_probe_total")
    stop = threading.Event()

    def writer(i):
        k = 0
        while not stop.is_set():
            h.observe(0.0001 * ((k % 100) + 1))
            h.labels(worker=str(i)).observe(0.25)
            c.labels(worker=str(i)).inc()
            k += 1

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    try:
        url = scope.url() + "/metrics"
        deadline = time.monotonic() + 2.0
        scrapes = 0
        while time.monotonic() < deadline:
            _status, _ct, body = _get(url)
            buckets, counts = _parse_histograms(body.decode())
            assert ("scope_torn_probe_seconds", "") in buckets
            for key, series in buckets.items():
                cums = [n for _le, n in series]
                assert cums == sorted(cums), (key, series)
                # the +Inf bucket IS the histogram count: both rendered
                # in the SAME scrape, so a torn read would desync them
                inf = [n for le, n in series if le == "+Inf"]
                assert inf and inf[0] == cums[-1], (key, series)
                if key in counts:
                    assert counts[key] == inf[0], (key, counts)
            scrapes += 1
        assert scrapes >= 5
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)


# ---------------------------------------------------------------------------
# /profilez on-demand device capture
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~25s device capture; ci static stage runs it by name
def test_profilez_capture_and_409_on_concurrent():
    scope.enable(port=0)
    tr = _trainer()
    x, y = _xy()
    tr.step(x, y)
    base = scope.url()

    status, armed = _get_json(base + "/profilez?steps=2&wait_s=0")
    assert status == 202 and armed["state"] == "armed"
    assert armed["completed"] is False

    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base + "/profilez?steps=1&wait_s=0")
    assert e.value.code == 409

    for _ in range(4):
        tr.step(x, y)
    _status, st = _get_json(base + "/profilez")
    assert st["state"] == "done" and st["error"] is None
    assert st["start_step"] == 2 and st["end_step"] == 4
    files = [os.path.join(dp, f)
             for dp, _dn, fs in os.walk(st["dir"]) for f in fs]
    assert files, f"empty trace dir {st['dir']}"
    assert mxprofiler.jax_trace_dir() is None   # session closed

    # the slot frees after completion: a new capture can arm
    status, again = _get_json(base + "/profilez?steps=1&wait_s=0")
    assert status == 202 and again["state"] == "armed"
    scope._state.abort_profile()


def test_profilez_rejects_bad_steps():
    scope.enable(port=0)
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(scope.url() + "/profilez?steps=0")
    assert e.value.code == 400


@pytest.mark.slow  # drives a trainer under a live capture; ci static runs it
def test_profilez_blocking_wait_returns_200():
    scope.enable(port=0)
    tr = _trainer()
    x, y = _xy()
    tr.step(x, y)
    done = threading.Event()
    out = {}

    def req():
        out["resp"] = _get_json(
            scope.url() + "/profilez?steps=2&wait_s=30")
        done.set()

    t = threading.Thread(target=req, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while not done.is_set() and time.monotonic() < deadline:
        tr.step(x, y)
    assert done.wait(5), "blocking profilez never returned"
    status, st = out["resp"]
    assert status == 200 and st["completed"] is True
    assert st["state"] == "done" and st["error"] is None


@pytest.mark.slow  # two full training runs; ci static runs it
def test_scope_on_loss_trajectory_bit_identical():
    """The acceptance gate: /profilez on a live trainer captures without
    pausing or reordering training — the loss trajectory is bit-identical
    with scope (and a capture) on vs off."""
    def run(with_scope):
        tr = _trainer(seed=0)
        rs = np.random.RandomState(7)
        batches = [(rs.randn(8, 8).astype(np.float32),
                    rs.randn(8, 4).astype(np.float32)) for _ in range(6)]
        losses = []
        for i, (xb, yb) in enumerate(batches):
            if with_scope and i == 2:
                _get_json(scope.url() + "/profilez?steps=2&wait_s=0")
            loss = tr.step(nd.array(xb), nd.array(yb))
            losses.append(float(np.asarray(loss.asnumpy(),
                                           np.float32)[()]))
        return losses

    ref = run(with_scope=False)
    scope.enable(port=0)
    got = run(with_scope=True)
    st = scope.profile_status()
    assert st and st["state"] == "done" and st["error"] is None
    assert got == ref, (got, ref)


# ---------------------------------------------------------------------------
# gang aggregator (in-process)
# ---------------------------------------------------------------------------

def test_aggregator_merges_names_stale_and_unreachable():
    launch = _load_launch()
    base = _free_port_block(n=3)
    st0, st1 = scope.ScopeState(rank=0), scope.ScopeState(rank=1)
    st0.note_step(None, 10)
    st1.note_step(None, 8)
    srv0 = scope.ScopeServer(st0, port=base + 1)
    srv1 = scope.ScopeServer(st1, port=base + 2)
    agg = launch._ScopeAggregator(base, 2, 0)
    try:
        _status, h = _get_json(f"http://127.0.0.1:{base}/healthz")
        assert h["ok"] is True and sorted(h["ranks"]) == ["0", "1"]

        _status, s = _get_json(
            f"http://127.0.0.1:{base}/statusz?stale_after=30")
        assert {r: p["step"] for r, p in s["ranks"].items()} \
            == {"0": 10, "1": 8}
        assert s["max_step"] == 10 and s["min_step"] == 8 \
            and s["step_spread"] == 2
        assert s["stale_ranks"] == [] and s["unreachable_ranks"] == []

        # rank 1 keeps ANSWERING but stops STEPPING (the wedged-collective
        # signature): only it goes stale once its last-step age passes
        # the threshold (rank 0 advances fast, so the rate-scaled
        # effective threshold stays at the requested floor)
        time.sleep(1.1)
        st0.note_step(None, 50)
        _status, s = _get_json(
            f"http://127.0.0.1:{base}/statusz?stale_after=1")
        assert s["stale_after_effective_s"] <= 1.0 + 1e-6
        assert s["stale_ranks"] == [1]
        assert s["unreachable_ranks"] == []

        _status, _ct, body = _get(f"http://127.0.0.1:{base}/metrics")
        text = body.decode()
        assert 'scope_rank_step{rank="0"} 50' in text
        assert 'scope_rank_reachable{rank="1"} 1' in text

        srv1.stop()
        _status, s = _get_json(f"http://127.0.0.1:{base}/statusz")
        assert s["unreachable_ranks"] == [1]
        assert "error" in s["ranks"]["1"]
        assert s["ranks"]["0"]["step"] == 50
    finally:
        agg.stop()
        srv0.stop()
        try:
            srv1.stop()
        except Exception:
            pass


def test_aggregator_stale_threshold_scales_with_step_cadence():
    """A healthy slow gang (seconds per step) must not read all-STALE
    between step boundaries: the stale floor scales by the fastest
    reported step rate, so only silence beyond ~5 step intervals
    convicts."""
    launch = _load_launch()
    base = _free_port_block(n=2)
    st0 = scope.ScopeState(rank=0)
    now = time.monotonic()
    # a 10 s/step rank, 8 s after its last boundary: legitimately idle
    st0._rate.append((now - 18.0, 1))
    st0._rate.append((now - 8.0, 2))
    st0.last_step = 2
    st0.last_step_mono = now - 8.0
    st0.last_step_wall = time.time()
    srv0 = scope.ScopeServer(st0, port=base + 1)
    agg = launch._ScopeAggregator(base, 1, 0)
    try:
        _status, s = _get_json(f"http://127.0.0.1:{base}/statusz")
        assert s["ranks"]["0"]["steps_per_s"] == 0.1
        assert s["stale_after_effective_s"] == 50.0    # 5 / 0.1
        assert s["stale_ranks"] == []                  # idle, not wedged
        # the same rank 60 s silent IS stale even at this cadence
        st0.last_step_mono = now - 60.0
        _status, s = _get_json(f"http://127.0.0.1:{base}/statusz")
        assert s["stale_ranks"] == [0]
        # an EXPLICIT ?stale_after= is used exactly — never out-scaled:
        # the operator asked for 5 s, the 8 s-silent rank is stale
        st0.last_step_mono = now - 8.0
        _status, s = _get_json(
            f"http://127.0.0.1:{base}/statusz?stale_after=5")
        assert s["stale_after_effective_s"] == 5.0
        assert s["stale_ranks"] == [0]
    finally:
        agg.stop()
        srv0.stop()


def test_ring_tail_returns_snapshots_not_live_records():
    """The /statusz scrape serializes ring records off-lock; they must
    be copies — annotate_step() mutates the newest live record and
    would otherwise race the HTTP thread's json.dumps."""
    diagnostics.enable()
    diagnostics.record_step(1, loss=0.5)
    tail = diagnostics.ring_tail(4)
    diagnostics.annotate_step(1, grad_norm=7.0)
    assert "grad_norm" not in tail[-1]           # snapshot, not a ref
    assert diagnostics.ring_tail(4)[-1]["grad_norm"] == 7.0
    assert diagnostics.ring_tail(0) == []


def test_aggregator_rejects_malformed_profilez_query():
    """A typo'd gang capture must fail the WHOLE request with 400 — not
    return 200 over N per-rank 400 bodies (a script gating on status
    would believe the capture started)."""
    launch = _load_launch()
    base = _free_port_block(n=2)
    st0 = scope.ScopeState(rank=0)
    srv0 = scope.ScopeServer(st0, port=base + 1)
    agg = launch._ScopeAggregator(base, 1, 0)
    try:
        for bad in ("steps=abc", "steps=1&wait_s=abc"):
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(f"http://127.0.0.1:{base}/profilez?{bad}")
            assert e.value.code == 400
        assert st0.profile_status() is None      # nothing armed anywhere
    finally:
        agg.stop()
        srv0.stop()


def test_aggregator_flags_error_answers_as_failing():
    """A rank answering 404/500 (older build, broken endpoint) is
    reachable but BROKEN: merged healthz must report ok=false and name
    it in failing_ranks — an error body must never read as healthy."""
    import http.server
    launch = _load_launch()
    base = _free_port_block(n=3)
    st0 = scope.ScopeState(rank=0)
    st0.note_step(None, 5)
    srv0 = scope.ScopeServer(st0, port=base + 1)

    class _Err(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps({"error": "no such endpoint"}).encode()
            self.send_response(404)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    bad = http.server.ThreadingHTTPServer(("127.0.0.1", base + 2), _Err)
    bad.daemon_threads = True
    t = threading.Thread(target=bad.serve_forever, daemon=True)
    t.start()
    agg = launch._ScopeAggregator(base, 2, 0)
    try:
        _status, h = _get_json(f"http://127.0.0.1:{base}/healthz")
        assert h["ok"] is False
        assert h["failing_ranks"] == [1]
        assert h["unreachable_ranks"] == []
        assert h["ranks"]["1"]["http_status"] == 404
        _status, s = _get_json(f"http://127.0.0.1:{base}/statusz")
        assert s["failing_ranks"] == [1]
        assert s["stale_ranks"] == [] and s["unreachable_ranks"] == []
        _status, _ct, body = _get(f"http://127.0.0.1:{base}/metrics")
        assert "scope_gang_failing_ranks 1" in body.decode()
    finally:
        agg.stop()
        srv0.stop()
        bad.shutdown()
        bad.server_close()


def test_aggregator_passes_through_rank_verdicts():
    """A rank answering 409/500 ANSWERED: the fan-out must hand its JSON
    verdict through annotated with the status code — never smear it
    into 'unreachable' (an operator must see 'capture busy', not a dead
    gang)."""
    launch = _load_launch()
    base = _free_port_block(n=2)
    st0 = scope.ScopeState(rank=0)
    st0.note_step(None, 3)
    st0.request_profile(2)            # /profilez now answers 409
    srv0 = scope.ScopeServer(st0, port=base + 1)
    agg = launch._ScopeAggregator(base, 1, 0)
    try:
        _status, prof = _get_json(
            f"http://127.0.0.1:{base}/profilez?steps=1&wait_s=0",
            timeout=30)
        assert prof["unreachable_ranks"] == []
        assert prof["ranks"]["0"]["http_status"] == 409
        assert "error" in prof["ranks"]["0"]
    finally:
        st0.abort_profile()
        agg.stop()
        srv0.stop()


@pytest.mark.slow  # waits out the full fan-out timeout; ci static runs it
def test_aggregator_not_wedged_by_silent_rank():
    """A rank whose port accepts connections but never answers (the
    wedge worse than a dead one) costs the fan-out one timeout, not the
    aggregator's liveness."""
    launch = _load_launch()
    launch_timeout = launch.SCOPE_FANOUT_TIMEOUT_S
    base = _free_port_block(n=3)
    st0 = scope.ScopeState(rank=0)
    st0.note_step(None, 5)
    srv0 = scope.ScopeServer(st0, port=base + 1)
    black_hole = socket.socket()
    black_hole.bind(("127.0.0.1", base + 2))
    black_hole.listen(1)          # accepts, never reads or writes
    agg = launch._ScopeAggregator(base, 2, 0)
    try:
        t0 = time.monotonic()
        _status, s = _get_json(f"http://127.0.0.1:{base}/statusz",
                               timeout=launch_timeout + 10)
        elapsed = time.monotonic() - t0
        assert s["unreachable_ranks"] == [1]
        assert s["ranks"]["0"]["step"] == 5
        assert elapsed < launch_timeout + 5, elapsed
    finally:
        agg.stop()
        srv0.stop()
        black_hole.close()


@pytest.mark.slow  # subprocess CLI round trip; ci static runs it
def test_scope_top_renders_once():
    launch = _load_launch()
    base = _free_port_block(n=2)
    st0 = scope.ScopeState(rank=0)
    st0.note_step(None, 42)
    srv0 = scope.ScopeServer(st0, port=base + 1)
    agg = launch._ScopeAggregator(base, 1, 0)
    try:
        r = subprocess.run(
            [sys.executable, SCOPE_TOP, "--port", str(base), "--once"],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "42" in r.stdout and "rank" in r.stdout
        assert "gen 0" in r.stdout and "world 1" in r.stdout
    finally:
        agg.stop()
        srv0.stop()


@pytest.mark.slow  # subprocess CLI round trip; ci static runs it
def test_scope_top_unreachable_aggregator_exits_nonzero():
    base = _free_port_block(n=1)
    r = subprocess.run(
        [sys.executable, SCOPE_TOP, "--port", str(base), "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "cannot reach" in r.stderr


# ---------------------------------------------------------------------------
# 2-rank launch smokes (slow; ci/run.sh sanity runs them)
# ---------------------------------------------------------------------------

_SCOPE_WORKER = """\
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + \
        " --xla_force_host_platform_device_count=8"
sys.path.insert(0, {root!r})
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, resilience, telemetry, diagnostics
from mxnet_tpu.gluon import nn, loss as gloss

rank = int(os.environ.get("JAX_PROCESS_ID", "0"))
base, total = sys.argv[1], int(sys.argv[2])
telemetry.enable()
diagnostics.enable()
resilience.install()
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                             {{"learning_rate": 0.1}})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
stop_flag = os.path.join(base, "stop")
while tr.num_update < total and not os.path.exists(stop_flag):
    tr.step(x, y)
    time.sleep(0.05)
print(f"rank {{rank}} done at step {{tr.num_update}}", flush=True)
"""


def _poll_json(url, timeout_s, predicate, per_req_timeout=10.0):
    """Poll `url` until predicate(payload) or deadline; returns the last
    payload (asserting the predicate held)."""
    deadline = time.monotonic() + timeout_s
    last, err = None, None
    while time.monotonic() < deadline:
        try:
            _status, last = _get_json(url, timeout=per_req_timeout)
            if predicate(last):
                return last
        except Exception as e:  # noqa: BLE001 - servers still starting
            err = e
        time.sleep(0.25)
    raise AssertionError(f"condition never held for {url}: "
                         f"last={last!r} err={err!r}")


@pytest.mark.slow  # several subprocess jax sessions; ci/run.sh runs it
def test_two_rank_scope_smoke(tmp_path):
    """Acceptance: a 2-rank --scope-port gang serves /healthz and
    /metrics on BOTH rank ports while training, the aggregator's
    /statusz names both ranks at (nearly) the same step, and a single
    aggregator /profilez?steps=2 produces a non-empty device-trace dir
    on every rank."""
    worker = tmp_path / "worker.py"
    worker.write_text(_SCOPE_WORKER.format(root=ROOT))
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    base = _free_port_block(n=3)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PROCESS_ID", "JAX_NUM_PROCESSES",
                        "MXNET_TPU_SCOPE", "MXNET_TPU_SCOPE_PORT")}
    proc = subprocess.Popen(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--scope-port", str(base),
         sys.executable, str(worker), str(run_dir), "100000"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        # both rank servers up and stepping
        for rank in (0, 1):
            h = _poll_json(
                f"http://127.0.0.1:{base + 1 + rank}/healthz", 240,
                lambda p: p.get("ok") and (p.get("step") or 0) >= 2)
            assert h["rank"] == rank
            _status, ct, body = _get(
                f"http://127.0.0.1:{base + 1 + rank}/metrics")
            assert ct.startswith("text/plain")
            assert "trainer_step_seconds_count" in body.decode()

        # aggregator gang view names both ranks, close in step
        s = _poll_json(
            f"http://127.0.0.1:{base}/statusz", 60,
            lambda p: sorted(p.get("ranks", {})) == ["0", "1"]
            and all(isinstance(r.get("step"), int)
                    for r in p["ranks"].values()))
        assert s["world_size"] == 2
        assert s["unreachable_ranks"] == [] and s["stale_ranks"] == []
        assert s["step_spread"] <= 20     # both alive and advancing

        # gang-wide on-demand capture through the aggregator
        _status, prof = _get_json(
            f"http://127.0.0.1:{base}/profilez?steps=2&wait_s=60",
            timeout=90)
        assert prof["unreachable_ranks"] == []
        for rank in ("0", "1"):
            st = prof["ranks"][rank]
            assert st["state"] == "done" and st["error"] is None, st
            files = [os.path.join(dp, f) for dp, _dn, fs
                     in os.walk(st["dir"]) for f in fs]
            assert files, f"rank {rank}: empty trace dir {st['dir']}"
    finally:
        (run_dir / "stop").write_text("")
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, proc.stdout.read()


@pytest.mark.slow  # several subprocess jax sessions; ci/run.sh runs it
def test_hang_statusz_stays_live_names_stale_rank(tmp_path):
    """Acceptance: under an injected hang@step on rank 1, the healthy
    rank's /statusz and the aggregator still answer within their
    timeouts, and the gang view names rank 1 as stale — a wedged peer
    never blocks the introspection plane."""
    worker = tmp_path / "worker.py"
    worker.write_text(_SCOPE_WORKER.format(root=ROOT))
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    base = _free_port_block(n=3)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PROCESS_ID", "JAX_NUM_PROCESSES",
                        "MXNET_TPU_SCOPE", "MXNET_TPU_SCOPE_PORT")}
    env["MXNET_TPU_FAULT_INJECT"] = "hang@step:3@rank:1"
    proc = subprocess.Popen(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--scope-port", str(base),
         sys.executable, str(worker), str(run_dir), "100000"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        # rank 1 wedges at step 3; rank 0 keeps stepping. The gang view
        # must say exactly that — from a server that answers promptly.
        def verdict(p):
            r0 = p.get("ranks", {}).get("0") or {}
            return p.get("stale_ranks") == [1] \
                and isinstance(r0.get("step"), int) and r0["step"] > 10
        s = _poll_json(
            f"http://127.0.0.1:{base}/statusz?stale_after=3", 300,
            verdict)
        assert s["unreachable_ranks"] == []          # wedged, not dead
        assert s["ranks"]["1"]["step"] <= 3          # where it hung
        # the wedged rank's own endpoint still answers too (its server
        # thread lives; only the trainer thread is stuck)
        t0 = time.monotonic()
        _status, h1 = _get_json(
            f"http://127.0.0.1:{base + 2}/healthz", timeout=10)
        assert time.monotonic() - t0 < 5
        assert h1["ok"] and h1["last_step_age_s"] > 3
        # and the healthy rank's full /statusz answers within budget
        t0 = time.monotonic()
        _status, s0 = _get_json(
            f"http://127.0.0.1:{base + 1}/statusz", timeout=10)
        assert time.monotonic() - t0 < 5
        assert s0["step"] > 10
    finally:
        (run_dir / "stop").write_text("")
        time.sleep(1.0)
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
