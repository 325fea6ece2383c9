"""mx.fleet tests: admission-aware placement (predict_429 against
published memsafe hints), health-routed load balancing with
bit-identical results across replicas, deterministic mid-stream
failover (tokens already streamed are never re-sent; the re-routed
stream matches an unloaded solo run bit-for-bit), zero-drop draining
(finish in-flight, requeue stragglers with replay), rolling updates
serving continuously, queue-wait autoscale hysteresis, the fleet=off
zero-overhead fast path, and the launcher-level replica supervision
smoke (slow)."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, fleet, parallel, resilience, serve
from mxnet_tpu.models import gpt as gpt_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAUNCH = os.path.join(ROOT, "tools", "launch.py")

_VOCAB = 128


@pytest.fixture(autouse=True)
def _clean_fleet():
    yield
    fleet.disable()
    serve.disable()
    resilience.uninstall()
    config.reset()


@pytest.fixture(scope="module")
def models():
    """TWO model instances with IDENTICAL weights (same seed before
    initialize): every fleet replica must generate bit-identically, and
    separate instances keep concurrent first-traces from sharing
    tracers across scheduler threads."""
    parallel.make_mesh(dp=-1)
    cfg = gpt_mod.gpt_tiny_config()
    out = []
    for _ in range(2):
        m = gpt_mod.GPTForCausalLM(cfg)
        mx.random.seed(0)
        m.initialize()
        out.append(m)
    return out


def _prompt(n, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, _VOCAB, (n,)).astype(np.int32)


class _Gang:
    """Two in-process replicas (own Server + ReplicaEndpoint each, on
    ephemeral ports) behind one Router — the single-process stand-in
    for the multi-process fleet."""

    def __init__(self, models, slots=2, **router_kw):
        self.servers = [serve.Server(m, slots=slots).start()
                        for m in models]
        self.eps = [fleet.ReplicaEndpoint(s, replica=i)
                    for i, s in enumerate(self.servers)]
        router_kw.setdefault("connect_timeout_s", 2.0)
        # a loaded 1-core CI box can stall a first decode past the 10s
        # production default; a spurious stall-failover makes the
        # placement asserts flaky (wedge detection has its own drill)
        router_kw.setdefault("stall_timeout_s", 120.0)
        self.router = fleet.Router(
            {i: ep.url for i, ep in enumerate(self.eps)}, **router_kw)
        self.router.poll_once()

    def close(self):
        self.router.stop()
        for ep in self.eps:
            ep.stop()
        for s in self.servers:
            s.stop()


@pytest.fixture()
def gang(models):
    g = _Gang(models)
    yield g
    g.close()


# -- admission prediction (pure) ---------------------------------------------

def _pool_statusz(free, headroom=10**9, page_size=8, buckets=None,
                  allocated=(), max_len=64):
    return {"admission": {"max_len": max_len, "slots": 2,
                          "queue_depth": 8, "buckets": buckets,
                          "page_size": page_size,
                          "pool_pages_free": free,
                          "headroom_bytes": headroom},
            "stats": {"buckets_allocated": list(allocated)}}


@pytest.mark.parametrize("st,need,want", [
    # 32 tokens are 4 pages of 8: two free pages cannot hold them
    (_pool_statusz(free=2), 32, True),
    (_pool_statusz(free=2), 16, False),              # exactly 2
    # a token past a page boundary is one more page
    (_pool_statusz(free=2), 17, True),
    # a live bucket group owns no cache: it promises a newcomer nothing
    (_pool_statusz(free=0, allocated=[16]), 10, True),
    # the bucket list does not enter: pages are counted by exact need
    (_pool_statusz(free=3, buckets=[24, 48]), 20, False),
    (_pool_statusz(free=2, buckets=[24, 48]), 20, True),
], ids=["over", "exact", "page-boundary", "live-bucket", "buckets-fit",
        "buckets-over"])
def test_predict_429_pool(st, need, want):
    assert fleet.Router.predict_429(st, need=need) is want


def test_predict_429_over_max_len():
    st = _pool_statusz(free=100, headroom=None)
    assert fleet.Router.predict_429(st, need=100) is True


def test_predict_429_unknown_headroom_predicts_nothing():
    # memsafe off -> headroom None -> never skip (admission control at
    # the replica stays the authority)
    st = _pool_statusz(free=0, headroom=None)
    assert fleet.Router.predict_429(st, need=10) is False


# -- routing -----------------------------------------------------------------

def test_fleet_routing_bit_identical(models, gang):
    p = _prompt(6)
    ref = models[0].generate(p[None], max_new_tokens=8,
                             on_device=False)[0].tolist()
    reqs = [gang.router.submit(p, max_new_tokens=8) for _ in range(4)]
    for r in reqs:
        assert r.result(timeout=60) == ref
        assert r.state == serve.DONE and r.verdict == "200 ok"
    # the load balancer spread the requests, it did not pin one replica
    tried = {r.replicas_tried[0] for r in reqs}
    assert tried == {0, 1}


def test_router_skips_drained_replica(gang):
    p = _prompt(5, seed=1)
    gang.router.drain(0)
    r = gang.router.submit(p, max_new_tokens=4)
    assert r.result(timeout=60) is not None
    assert 0 not in r.replicas_tried
    gang.router.undrain(0)
    gang.router.poll_once()
    gang.router.drain(1)
    r2 = gang.router.submit(p, max_new_tokens=4)
    assert r2.result(timeout=60) is not None
    # every attempt must land on 0 (1 is draining); a retry on 0 itself
    # is allowed — a slow box can trip the stall bound mid-stream
    assert set(r2.replicas_tried) == {0}
    gang.router.undrain(1)


def test_statusz_publishes_admission_hints(gang):
    st = gang.eps[0].statusz()
    hints = st["admission"]
    assert hints["slots"] == 2 and hints["max_len"] >= 1
    assert "headroom_bytes" in hints and hints["page_size"] >= 1
    view = gang.router.statusz()
    assert set(view["replicas"]) == {0, 1}


# -- failover ----------------------------------------------------------------

def test_failover_mid_stream_bit_identical(models, gang):
    """Kill a replica mid-generation under load: the re-routed
    request's full token stream must be bit-identical to an unloaded
    solo run, and already-streamed tokens are never re-sent (the
    replayed stream starts at the high-water mark — a duplicate would
    break the equality)."""
    p = _prompt(8, seed=2)
    ref = models[0].generate(p[None], max_new_tokens=24,
                             on_device=False)[0].tolist()
    # slow the victim's streaming so the kill lands mid-stream
    gang.eps[0]._slow_ms, gang.eps[0]._slow_checked = 25.0, True
    gang.router.drain(1, remote=False)      # pin placement to replica 0
    r = gang.router.submit(p, max_new_tokens=24)
    deadline = time.monotonic() + 30
    while len(r.tokens) < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(r.tokens) >= 3, "stream never started"
    pre_kill = len(r.tokens)
    gang.router.undrain(1, remote=False)    # open the survivor
    gang.eps[0].kill()
    assert r.result(timeout=60) == ref
    assert r.state == serve.DONE and r.verdict == "200 ok"
    assert r.failovers == 1 and r.replicas_tried == [0, 1]
    assert pre_kill < 24                    # the kill was mid-stream


def _fake_replica(submit_fn):
    """A stdlib HTTP stand-in for a replica endpoint: /healthz answers
    ok, /submit streams whatever ndjson lines `submit_fn(body)` yields.
    Lets the replay protocol be pinned without timing games."""
    from http.server import BaseHTTPRequestHandler as _BH
    from http.server import ThreadingHTTPServer as _TS

    class Handler(_BH):
        protocol_version = "HTTP/1.0"

        def log_message(self, *args):
            pass

        def do_GET(self):  # noqa: N802
            body = json.dumps({"ok": True}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):  # noqa: N802
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
            self.send_response(200)
            self.end_headers()
            for line in submit_fn(body):
                self.wfile.write((json.dumps(line) + "\n").encode())
                self.wfile.flush()

    httpd = _TS(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def test_drain_requeue_replays_with_skip_high_water():
    """The drain-expiry requeue contract, pinned at the protocol level:
    the first attempt streams 5 tokens then a retriable cancellation
    (a drain whose grace expired mid-generation); the router must
    replay on a survivor with skip == the high-water mark, so the
    client's concatenated stream has every token exactly once."""
    ref = list(range(100, 112))
    seen_skips = []

    def submit(body):
        skip = int(body.get("skip", 0))
        seen_skips.append(skip)
        if len(seen_skips) == 1:
            for t in ref[:5]:
                yield {"t": t}
            yield {"done": True, "state": "cancelled",
                   "verdict": "499 cancelled: drain grace expired",
                   "n": 5, "retriable": True}
        else:
            for t in ref[skip:]:
                yield {"t": t}
            yield {"done": True, "state": "done", "verdict": "200 ok",
                   "n": len(ref)}

    a, url_a = _fake_replica(submit)
    b, url_b = _fake_replica(submit)
    try:
        router = fleet.Router({0: url_a, 1: url_b})
        for rep in router._replicas.values():
            rep.healthy = True
        r = router.submit([1, 2, 3], max_new_tokens=12)
        assert r.result(timeout=30) == ref
        assert r.state == serve.DONE and r.verdict == "200 ok"
        assert r.failovers == 1
        assert seen_skips == [0, 5]     # replay resumed at high water
    finally:
        a.shutdown()
        b.shutdown()


def test_drain_finishes_inflight_within_grace(gang):
    """A drain with grace finishes in-flight work locally — nothing is
    requeued, nothing is dropped."""
    p = _prompt(5, seed=4)
    gang.router.drain(1, remote=False)
    r = gang.router.submit(p, max_new_tokens=6)
    deadline = time.monotonic() + 30
    while not r.tokens and time.monotonic() < deadline:
        time.sleep(0.005)
    gang.router.undrain(1, remote=False)
    finished, requeued = gang.eps[0].drain_and_requeue(grace_s=20.0)
    assert requeued == 0
    assert r.result(timeout=60) is not None
    assert r.state == serve.DONE and r.verdict == "200 ok"
    # the drained replica finished the request locally ("finished" at
    # drain-return time can race the handler's terminal-line write, so
    # assert on the settled counter, not the snapshot)
    deadline = time.monotonic() + 10
    while gang.eps[0]._served < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert gang.eps[0]._served >= 1


def test_draining_replica_rejects_new_submits_retriable(gang):
    gang.eps[0].begin_drain()
    gang.eps[1].begin_drain()
    r = gang.router.submit(_prompt(4, seed=5), max_new_tokens=4)
    r.result(timeout=60)
    assert r.state in (serve.SHED, serve.FAILED)
    assert "503" in (r.verdict or "")
    gang.eps[0].draining = gang.eps[1].draining = False


# -- rolling update ----------------------------------------------------------

@pytest.mark.slow  # ~60s of live rolling restarts; ci fleet stage runs it by name
def test_rolling_update_serves_continuously(models, gang):
    p = _prompt(6, seed=6)
    ref = models[0].generate(p[None], max_new_tokens=6,
                             on_device=False)[0].tolist()
    stop = threading.Event()
    results = []

    def client():
        while not stop.is_set():
            r = gang.router.submit(p, max_new_tokens=6)
            results.append((r, r.result(timeout=60)))

    th = threading.Thread(target=client)
    th.start()
    try:
        def update(rid):
            gang.eps[rid].version = "v2"     # new weights stand-in

        updated = gang.router.rolling_update(update, version="v2",
                                             wait_timeout_s=30.0)
    finally:
        stop.set()
        th.join(timeout=60)
    assert updated == [0, 1]
    assert len(results) >= 1
    for r, toks in results:
        assert r.state == serve.DONE and toks == ref
    view = gang.router.statusz()["replicas"]
    assert all(v["version"] == "v2" for v in view.values())


# -- autoscale ---------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_autoscale_hysteresis():
    asked = []
    clk = _Clock()
    r = fleet.Router({0: "http://x0", 1: "http://x1"}, autoscale=True,
                     autoscale_p99_ms=100.0, autoscale_window_s=5.0,
                     on_scale=asked.append, clock=clk)

    def set_pressure(p99_ms, queued):
        for rep in r._replicas.values():
            rep.healthy = True
            rep.stats = {"queue_wait_p99_ms": p99_ms,
                         "stats": {"queued": queued}}

    set_pressure(500.0, 3)
    r.maybe_autoscale(now=0.0)
    r.maybe_autoscale(now=2.0)
    assert asked == []                      # window not sustained yet
    r.maybe_autoscale(now=5.5)
    assert asked == [3]                     # grow by one
    # a blip below threshold resets the hysteresis timer
    set_pressure(10.0, 1)
    r.maybe_autoscale(now=6.0)
    set_pressure(500.0, 3)
    r.maybe_autoscale(now=7.0)
    r.maybe_autoscale(now=9.0)
    assert asked == [3]                     # timer restarted at 7.0
    # sustained quiet (low p99 AND empty queues) gives one back
    set_pressure(1.0, 0)
    r.maybe_autoscale(now=20.0)
    r.maybe_autoscale(now=26.0)
    assert asked == [3, 1]
    assert [e["dir"] for e in r.scale_events] == ["up", "down"]


def test_autoscale_needs_every_replica_hot():
    asked = []
    clk = _Clock()
    r = fleet.Router({0: "u0", 1: "u1"}, autoscale=True,
                     autoscale_p99_ms=100.0, autoscale_window_s=1.0,
                     on_scale=asked.append, clock=clk)
    reps = list(r._replicas.values())
    for rep in reps:
        rep.healthy = True
    reps[0].stats = {"queue_wait_p99_ms": 900.0, "stats": {"queued": 5}}
    reps[1].stats = {"queue_wait_p99_ms": 5.0, "stats": {"queued": 0}}
    r.maybe_autoscale(now=0.0)
    r.maybe_autoscale(now=2.0)
    # one hot replica is a ROUTING problem, not a capacity problem
    assert asked == []


# -- fleet=off fast path ------------------------------------------------------

def test_fleet_off_zero_overhead(models, monkeypatch):
    from mxnet_tpu import scope
    assert fleet.enabled() is False
    calls = []
    monkeypatch.setattr(fleet, "snapshot",
                        lambda: calls.append(1) or {"endpoints": []})
    assert scope._fleet_section() is None   # off: one bool check
    srv = serve.Server(models[0], slots=2)
    r = srv.submit(_prompt(4, seed=7), max_new_tokens=4)
    srv.drain()
    assert r.state == serve.DONE
    srv.stop()
    assert calls == []                      # serving never touched fleet
    fleet.enable()
    assert scope._fleet_section() is not None
    assert calls == [1]


# -- launcher supervision (subprocess) ----------------------------------------

@pytest.mark.slow
def test_launch_fleet_supervises_replicas(tmp_path):
    """End-to-end replica supervision: SIGKILL one replica of a live
    launcher fleet mid-request — zero accepted requests lost (the
    stream completes via failover), restarts.jsonl records the
    replica_exit/replica_relaunch pair, and launcher SIGTERM drains
    both replicas through the preemption path."""
    port = 8971
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_SERVE="on")
    proc = subprocess.Popen(
        [sys.executable, LAUNCH, "--serve-replicas", "2",
         "--fleet-port", str(port), "--diagnostics-dir", str(tmp_path),
         "--max-restarts", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)

    def get(path, p=port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{p}{path}", timeout=5) as resp:
            return json.loads(resp.read())

    try:
        deadline = time.time() + 240
        while time.time() < deadline:
            try:
                h = get("/healthz")
                if all(v["ok"] for v in h["replicas"].values()):
                    break
            except Exception:
                pass
            time.sleep(0.5)
        else:
            pytest.fail("fleet replicas never became healthy")

        import http.client
        def submit(n):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            body = json.dumps({"prompt": list(range(1, 8)),
                               "max_new_tokens": n}).encode()
            conn.request("POST", "/submit", body)
            resp = conn.getresponse()
            toks, final = [], None
            while True:
                line = resp.readline()
                if not line:
                    break
                rec = json.loads(line)
                if "t" in rec:
                    toks.append(rec["t"])
                if rec.get("done"):
                    final = rec
                    break
            conn.close()
            return toks, final

        ref, fin = submit(16)
        assert fin["state"] == "done" and len(ref) == 16

        pids = {rid: get("/statusz", p=port + 1 + rid)["pid"]
                for rid in (0, 1)}
        results = []
        th = threading.Thread(
            target=lambda: results.append(submit(24)))
        th.start()
        time.sleep(0.5)
        os.kill(pids[0], signal.SIGKILL)
        os.kill(pids[1], 0)                 # survivor still alive
        th.join(timeout=180)
        assert results, "request under kill never completed"
        toks, final = results[0]
        assert final["state"] == "done" and len(toks) == 24

        deadline = time.time() + 90
        kinds = []
        while time.time() < deadline:
            rj = tmp_path / "restarts.jsonl"
            if rj.exists():
                kinds = [json.loads(l)["kind"]
                         for l in rj.read_text().splitlines() if l]
                if "replica_relaunch" in kinds:
                    break
            time.sleep(0.5)
        assert "replica_exit" in kinds and "replica_relaunch" in kinds
    finally:
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 143           # 128 + SIGTERM
    assert "drained" in out and "preemption path" in out
