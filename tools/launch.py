#!/usr/bin/env python
"""Distributed job launcher (reference: `tools/launch.py` +
`3rdparty/dmlc-core/tracker/` ssh/local launchers).

The reference spawns scheduler + server + worker processes and wires them
with DMLC_* env vars for the ps-lite transport. The TPU-native cluster model
is SPMD under a single controller per host: every process runs the SAME
training script, jax.distributed connects them through a coordinator, and
XLA collectives replace the parameter server. So this launcher:

  * spawns `-n` worker processes (locally or over ssh to `-H` hosts),
  * wires them with JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID (read by `jax.distributed.initialize()` and by
    `mxnet_tpu.parallel.init_distributed()`),
  * also exports the DMLC_* names so reference scripts that inspect
    `kv.rank` / `kv.num_workers` keep working,
  * prefixes every worker output line with `[rank N]` so interleaved
    multi-rank logs stay attributable, and — with `--diagnostics-dir` —
    tees each worker's raw output to `<dir>/<rank>/worker.log` and points
    `mx.diagnostics` at `<dir>` so crashes leave
    `<dir>/<rank>/postmortem.json` (merge with tools/postmortem_report.py),
  * exits with the FIRST nonzero worker exit code (by rank) instead of
    flattening every failure to 1,
  * with `--max-restarts N` supervises the gang: when any rank dies it
    tears down the peers, backs off exponentially, and relaunches the
    whole gang (workers running mx.resilience with resume='auto' then
    continue from the last good checkpoint); restart events append to
    `<diagnostics-dir>/restarts.jsonl` with the per-generation world
    size and surviving-worker set,
  * with `--trace-dir` arms mx.trace in every worker against ONE shared
    gang trace epoch, so the per-rank `<dir>/<rank>/trace.jsonl` span
    files merge into a single clock-aligned timeline
    (`tools/trace_report.py` renders the Perfetto trace and the
    gang-wide straggler verdict),
  * with `--elastic` (plus `--min-workers M`) the relaunch happens at
    the SURVIVING world size instead of the original shape: ranks that
    lost their slot (signal death, preemption save, injected
    shrink@step) shrink the gang, an EXIT_GROW request grows it back
    toward `-n`; workers resuming with mx.resilience reshard='auto'
    redistribute the checkpoint onto the new topology
    (`tools/postmortem_report.py` renders the reshape history),
  * with `--scope-port P` arms mx.scope live introspection in every
    worker — rank R serves /healthz /metrics /statusz /tracez /profilez
    on port P+1+R — and runs a gang AGGREGATOR on the base port P that
    fans out to the per-rank endpoints with short timeouts (a wedged
    rank can never wedge the aggregator), merges `/statusz` into one
    gang view naming stale/unreachable ranks, and proxies
    `/profilez?steps=N` to every rank at once for a gang-wide device
    capture (`tools/scope_top.py` polls it and renders a live one-screen
    summary),
  * with `--heartbeat-timeout S` arms mx.guard liveness in every worker
    and polls the per-rank heartbeat files: a rank whose beat goes stale
    (stuck host, wedged collective — alive but making no progress) is
    SIGKILLed so the relaunch machinery treats it as an ordinary slot
    loss; a worker that exits EXIT_PEER_LOST (86 — its mx.guard
    collective deadline named a dead peer) is relaunched like any other
    failure,
  * with `--serve-replicas N` runs a REPLICATED SERVING GANG instead of
    a training job: N independent `mxnet_tpu.fleet` replica workers
    (each one serve.Server with an HTTP endpoint on
    `--fleet-port`+1+R) behind the fleet router's health-routed front
    door on `--fleet-port`. A dead replica is relaunched ALONE
    (restarts.jsonl records replica_exit / replica_relaunch) while the
    router replays its in-flight requests on survivors bit-identically;
    SIGTERM drains every replica before exit (zero-drop), POST /roll
    rolls the fleet replica-by-replica onto new weights, and
    MXNET_TPU_FLEET_AUTOSCALE=on resizes the fleet on sustained p99
    queue wait between `--min-workers` and `--max-replicas`.

`-s` (servers) is accepted and ignored with a warning: there are no
parameter servers on TPU (SURVEY.md §2.5).

Usage:
  python tools/launch.py -n 4 --launcher local python train.py
  python tools/launch.py -n 2 --diagnostics-dir diag python train.py
  python tools/launch.py -n 2 -H hosts.txt --launcher ssh python train.py
  python tools/launch.py --serve-replicas 2 --diagnostics-dir diag
"""
from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

# the launcher must stay import-light (no jax, no mxnet_tpu package
# import), but its locks ride the same mx.check tsan-lite analysis as the
# framework's: load the stdlib-only instrumented-lock module directly by
# path. Any failure falls back to plain threading primitives.
def _load_locklint():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "mxnet_tpu", "_locklint.py")
    spec = importlib.util.spec_from_file_location("mx_locklint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


try:
    _locklint = _load_locklint()
    _make_lock = _locklint.make_lock
except Exception:   # pragma: no cover - standalone copy of this script
    _make_lock = lambda name: threading.Lock()   # noqa: E731  # mx.check: disable=raw-lock

# serializes the pump threads' line writes onto the launcher's stdout:
# one lock, taken per line — without it two ranks' prefixed lines can
# interleave mid-write on a pipe (found by adopting the mx.check
# instrumented-lock sweep here; the per-rank worker.log tees stay
# single-writer and need no lock)
_out_lock = _make_lock("launch.stdout")

# mirrors of mxnet_tpu.resilience exit codes (the launcher must stay
# import-light — no jax): a worker exiting EXIT_PREEMPTED saved a final
# checkpoint on SIGTERM and is safe to relaunch; EXIT_SHRINK/EXIT_GROW
# are elastic reshape requests (state saved, relaunch the gang smaller /
# larger — honored with --elastic)
EXIT_PREEMPTED = 83
EXIT_SHRINK = 84
EXIT_GROW = 85
# a HEALTHY rank concluded a peer died inside a blocking collective
# (mx.guard collective deadline) and exited so the gang can relaunch —
# the actually-dead peer is the slot loss, not this rank
EXIT_PEER_LOST = 86
HEARTBEAT_FILE = "heartbeat.json"

# seconds an elastic supervisor keeps polling after the FIRST failure
# before snapshotting exit codes: co-failing ranks (a slice losing several
# workers at once) land in the same generation instead of causing one
# single-step shrink per relaunch. The window closes early once every
# rank has exited
ELASTIC_SETTLE_S = 3.0


def build_env(rank, num_workers, coordinator, diagnostics_dir=None,
              restart_count=0, trace_dir=None, trace_epoch_ns=None,
              heartbeat_timeout=None, scope_port=0, goodput_dir=None):
    # NOTE: workers get no device assignment. Local gangs (-n N,
    # --serve-replicas) are CPU-tested only: on a chip host every local
    # worker would claim every chip, and a chip belongs to one process at
    # a time. One process per host drives all its chips (README,
    # "Running on the CPU and on the chip").
    if ":" not in coordinator:
        coordinator = coordinator + ":9876"  # default coordination port
    env = dict(os.environ)
    env.update({
        "JAX_COORDINATOR_ADDRESS": coordinator,
        "JAX_NUM_PROCESSES": str(num_workers),
        "JAX_PROCESS_ID": str(rank),
        # reference-compat names (read by kvstore facade / user scripts)
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_NUM_SERVER": "0",
        "DMLC_WORKER_ID": str(rank),
        "DMLC_PS_ROOT_URI": coordinator.split(":")[0],
        "DMLC_PS_ROOT_PORT": coordinator.split(":")[1],
        # supervised-relaunch generation (read by mx.resilience: feeds the
        # restarts_total counter and disarms one-shot fault injections)
        "MXNET_TPU_RESTART_COUNT": str(restart_count),
    })
    if diagnostics_dir:
        # arm mx.diagnostics in every worker: the module appends /<rank>
        # (from JAX_PROCESS_ID) so ranks never clobber each other's dumps
        env["MXNET_TPU_DIAGNOSTICS"] = "1"
        env["MXNET_TPU_DIAGNOSTICS_DIR"] = diagnostics_dir
    if trace_dir:
        # arm mx.trace in every worker (per-rank span files under
        # <dir>/<rank>/trace.jsonl) and export ONE shared gang trace
        # epoch: every rank records its own wall-clock offset against it
        # in its meta line, so tools/trace_report.py aligns all ranks on
        # a single timeline. The epoch is fixed per launcher lifetime —
        # relaunched generations stay on the same axis.
        env["MXNET_TPU_TRACE"] = "on"
        env["MXNET_TPU_TRACE_DIR"] = trace_dir
        if trace_epoch_ns is not None:
            env["MXNET_TPU_TRACE_EPOCH_NS"] = str(trace_epoch_ns)
    if goodput_dir:
        # arm mx.goodput in every worker (per-rank interval files under
        # <dir>/<rank>/goodput.jsonl). The gang epoch is SHARED with
        # mx.trace (one wall timestamp, fixed across relaunch
        # generations) so tools/goodput_report.py's chrome badput lane
        # lands on the same axis as trace_report's timeline
        env["MXNET_TPU_GOODPUT"] = "on"
        env["MXNET_TPU_GOODPUT_DIR"] = goodput_dir
        if trace_epoch_ns is not None:
            env.setdefault("MXNET_TPU_TRACE_EPOCH_NS", str(trace_epoch_ns))
    if heartbeat_timeout:
        # arm mx.guard in every worker: per-rank liveness heartbeats
        # under <diagnostics_dir>/<rank>/heartbeat.json, which the
        # supervisor's staleness poll ages against this same timeout
        env["MXNET_TPU_GUARD"] = "1"
        env["MXNET_TPU_HEARTBEAT_TIMEOUT_S"] = str(heartbeat_timeout)
    if scope_port:
        # arm mx.scope in every worker: rank R serves its introspection
        # endpoints on base+1+R (the base port is the launcher-side gang
        # aggregator's)
        env["MXNET_TPU_SCOPE"] = "on"
        env["MXNET_TPU_SCOPE_PORT"] = str(int(scope_port) + 1 + rank)
    return env


def _pump(stream, rank, tee_file):
    """Forward one worker's merged stdout/stderr line-by-line, prefixed
    with its rank; raw (unprefixed) lines tee into the per-rank log."""
    prefix = f"[rank {rank}] "
    for line in stream:
        with _out_lock:
            sys.stdout.write(prefix + line)
            sys.stdout.flush()
        if tee_file is not None:
            tee_file.write(line)
            tee_file.flush()
    stream.close()
    if tee_file is not None:
        tee_file.close()


def _spawn(command, env, rank, diagnostics_dir, extra_args=(),
           restart_count=0):
    tee = None
    if diagnostics_dir:
        rank_dir = os.path.join(diagnostics_dir, str(rank))
        os.makedirs(rank_dir, exist_ok=True)
        # relaunches APPEND: truncating would erase the crash output the
        # supervised-restart feature exists to preserve
        tee = open(os.path.join(rank_dir, "worker.log"),
                   "a" if restart_count else "w")
        if restart_count:
            tee.write(f"=== relaunch attempt {restart_count} ===\n")
            tee.flush()
    proc = subprocess.Popen(
        list(extra_args) + list(command), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, errors="replace", bufsize=1)
    pump = threading.Thread(target=_pump, args=(proc.stdout, rank, tee),
                            daemon=True)
    pump.start()
    return proc, pump


def _terminate_gang(procs, pumps, sig=signal.SIGTERM, grace=10.0):
    """Tear a gang down cleanly: forward `sig` to every live worker (so a
    preemption-aware worker gets its grace window), wait up to `grace`
    seconds, SIGKILL stragglers, reap every child (no zombies), and join
    the pump threads so the worker.log tees are flushed and closed (no
    lost tail output)."""
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(sig)
            except OSError:
                pass
    deadline = time.monotonic() + grace
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
    for t in pumps:
        t.join(timeout=5.0)


def _reap(procs, pumps, early_exit=False, killed=None):
    """Wait for the workers (polling — a signal handler must never call a
    blocking Popen.wait the interrupted main thread already sits in: the
    shared _waitpid_lock deadlocks). Returns (exit_code, failing_rank):
    exit_code is the FIRST nonzero code by rank (the acceptance contract:
    a CI wrapper sees the real failure code, not a flattened 1), or 0.
    With `early_exit` (supervised-relaunch mode) it returns as soon as
    ANY worker fails, leaving the peers running for the caller to tear
    down. `killed` is the flag dict the signal handler sets: seeing it,
    the loop forwards the signal to the gang, reaps, flushes the tee
    pumps, and exits 128+signum."""
    while True:
        if killed and killed.get("sig"):
            sig = killed["sig"]
            _terminate_gang(procs, pumps, sig=signal.Signals(sig))
            sys.exit(128 + sig)
        codes = [p.poll() for p in procs]
        if early_exit:
            bad = [(r, c) for r, c in enumerate(codes)
                   if c is not None and c != 0]
            if bad:
                rank, code = bad[0]
                print(f"worker {rank} exited with code {code}",
                      file=sys.stderr)
                return code, rank
        if all(c is not None for c in codes):
            for t in pumps:
                t.join(timeout=5.0)
            first_bad, bad_rank = 0, None
            for rank, code in enumerate(codes):
                if code != 0:
                    print(f"worker {rank} exited with code {code}",
                          file=sys.stderr)
                    if first_bad == 0:
                        first_bad, bad_rank = code, rank
            return first_bad, bad_rank
        time.sleep(0.2)


def _log_restart(diagnostics_dir, event):
    """Restart events feed the same observability surfaces as everything
    else: stderr for the operator, <diagnostics_dir>/restarts.jsonl for
    tools (the workers' own telemetry counts restarts_total from
    MXNET_TPU_RESTART_COUNT; tools/postmortem_report.py renders the
    reshape history from the per-generation world sizes recorded here)."""
    kind = {EXIT_PREEMPTED: "preempted", EXIT_SHRINK: "requested shrink",
            EXIT_GROW: "requested grow",
            EXIT_PEER_LOST: "lost a peer (collective deadline)",
            }.get(event["exit_code"], "failed")
    reshape = ""
    if event.get("new_world_size") != event.get("world_size"):
        reshape = (f" at world size {event['new_world_size']} "
                   f"(was {event['world_size']})")
    print(f"launch: rank {event['failed_rank']} {kind} with code "
          f"{event['exit_code']} — tearing down the gang and relaunching"
          f"{reshape} in {event['backoff_s']:.1f}s "
          f"(restart {event['attempt']})",
          file=sys.stderr)
    _append_restart_event(diagnostics_dir, event)


def _append_restart_event(diagnostics_dir, event):
    """Append one record to <diagnostics_dir>/restarts.jsonl (the
    single supervision log: restart events and stale-heartbeat kills
    share it, so tools/postmortem_report.py renders one history)."""
    if not diagnostics_dir:
        return
    try:
        os.makedirs(diagnostics_dir, exist_ok=True)
        with open(os.path.join(diagnostics_dir, "restarts.jsonl"), "a") as f:
            f.write(json.dumps(event) + "\n")
    except OSError as e:
        print(f"launch: cannot record {event.get('kind', 'restart')} "
              f"event: {e}", file=sys.stderr)


class _HeartbeatMonitor:
    """Supervisor-side liveness poll (--heartbeat-timeout): ages every
    rank's mx.guard heartbeat file and SIGKILLs a stuck-but-alive worker
    whose beat goes stale — turning an invisible hang (a wedged host
    blocking its peers inside a collective) into an ordinary slot loss
    the --elastic relaunch path already handles, instead of waiting on
    the cluster scheduler. A rank that has not yet written a
    CURRENT-GENERATION beat is left alone (startup and first compile
    legitimately precede the first step), and every kill is recorded in
    <diagnostics_dir>/restarts.jsonl as a stale_heartbeat event.

    At most ONE rank is killed per generation — the OLDEST stale beat.
    When one rank wedges a blocking collective, every peer blocks behind
    it and ALL their beats go stale nearly simultaneously; the wedged
    rank stopped beating first, so it ages out first, and killing only
    it keeps the healthy-but-blocked peers out of the slot-loss
    accounting (they die to the ordinary teardown and relaunch at full
    surviving strength — an elastic gang shrinks by one, not by the
    whole blocked membership). A second simultaneous wedge is caught by
    the next generation's monitor."""

    def __init__(self, procs, diagnostics_dir, timeout_s, generation):
        self.procs = procs
        self.dir = diagnostics_dir
        self.timeout = float(timeout_s)
        self.gen = generation
        self.killed = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="launch-heartbeat-poll",
                                        daemon=True)
        self._thread.start()

    def _read(self, rank):
        path = os.path.join(self.dir, str(rank), HEARTBEAT_FILE)
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            # missing or torn beat: the workers write atomically, so
            # this is "no evidence", never "stale evidence"
            return None

    def _run(self):
        interval = max(0.25, min(1.0, self.timeout / 4.0))
        while not self._stop.wait(interval):
            now = time.time()
            worst = None
            for rank, p in enumerate(self.procs):
                if p.poll() is not None:
                    continue
                rec = self._read(rank)
                if not rec or rec.get("gen") != self.gen:
                    continue
                age = now - float(rec.get("ts", now))
                if age <= self.timeout:
                    continue
                if worst is None or age > worst[0]:
                    worst = (age, rank, p, rec)
            if worst is None:
                continue
            age, rank, p, rec = worst
            self.killed.append(rank)
            print(f"launch: rank {rank} heartbeat stale ({age:.1f}s > "
                  f"{self.timeout:.1f}s; last beat step "
                  f"{rec.get('step')}, phase {rec.get('phase') or '?'})"
                  " — killing the stuck worker (slot loss; the "
                  "supervisor relaunches the gang)", file=sys.stderr)
            try:
                p.send_signal(signal.SIGKILL)
            except OSError:
                pass
            _append_restart_event(self.dir, {
                "ts": now, "kind": "stale_heartbeat",
                "rank": rank, "age_s": round(age, 3),
                "timeout_s": self.timeout,
                "generation": self.gen,
                "last_step": rec.get("step"),
                "phase": rec.get("phase")})
            # one kill per generation: stop polling — the reap sees the
            # death, tears the gang down, and the NEXT generation gets a
            # fresh monitor (killing every stale beat in one pass would
            # also reap the healthy peers blocked behind the wedged
            # rank's collective, over-shrinking an elastic gang)
            return

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5.0)


# per-rank fetch budget for the aggregator's /healthz and /statusz
# fan-out: short and hard — a wedged rank costs one timeout, never the
# aggregator's liveness (/profilez uses its own wait_s + margin instead,
# a capture legitimately spans several steps)
SCOPE_FANOUT_TIMEOUT_S = 2.0
# a rank whose last completed step is older than this reads as STALE in
# the merged gang view (override per request with ?stale_after=S)
SCOPE_STALE_AFTER_S = 5.0


class _ScopeAggregator:
    """Gang introspection aggregator (--scope-port): one HTTP server on
    the base port that fans out to the per-rank mx.scope servers
    (base+1+rank) and merges the answers.

      /healthz   — per-rank liveness, unreachable/failing ranks named
      /statusz   — the merged gang view: per-rank step/rate/headroom,
                   stale ranks named by last-step / heartbeat age
                   (default threshold scales with the gang's step
                   cadence; an explicit ?stale_after=S is used exactly)
      /metrics   — gang-level Prometheus gauges derived from the fan-out
                   (per-rank step/age/reachability; scrape the per-rank
                   ports directly for the full telemetry registries —
                   identical metric names from N ranks cannot legally
                   merge into one exposition page)
      /profilez  — proxied to EVERY rank at once (query passed through):
                   one request arms a gang-wide device capture

    Every fan-out runs one thread per rank with a hard per-rank timeout,
    so a wedged or dead rank degrades to an 'unreachable' entry — it can
    never wedge the aggregator (the acceptance gate under an injected
    hang). Stdlib-only, jax-free, like the rest of this launcher."""

    def __init__(self, base_port, world, generation, host="127.0.0.1"):
        self.host = host
        self.base_port = int(base_port)
        self.world = int(world)
        self.generation = int(generation)
        handler = self._make_handler()
        self.httpd = ThreadingHTTPServer((host, self.base_port), handler)
        self.httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, kwargs={"poll_interval": 0.2},
            name="launch-scope-aggregator", daemon=True)
        self._thread.start()
        print(f"launch: mx.scope gang aggregator on http://{host}:"
              f"{self.base_port} (ranks on "
              f"{self.base_port + 1}..{self.base_port + self.world})",
              file=sys.stderr)

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(timeout=5.0)

    # -- fan-out ---------------------------------------------------------
    def rank_url(self, rank, path):
        return f"http://{self.host}:{self.base_port + 1 + rank}{path}"

    def _fetch(self, rank, path, timeout):
        try:
            with urllib.request.urlopen(self.rank_url(rank, path),
                                        timeout=timeout) as r:
                return json.load(r), None
        except urllib.error.HTTPError as e:
            # the rank ANSWERED: a 409 (capture busy) or 500 is a
            # verdict with a JSON body, not a dead peer — pass it
            # through annotated instead of smearing it into
            # 'unreachable' (the operator must see 'busy', not 'dead')
            try:
                body = json.load(e)
            except Exception:
                body = None
            if isinstance(body, dict):
                body.setdefault("http_status", e.code)
                return body, None
            return None, f"HTTP {e.code}"
        except Exception as e:  # noqa: BLE001 - any failure = unreachable
            return None, f"{type(e).__name__}: {e}"

    def fan_out(self, path, timeout=SCOPE_FANOUT_TIMEOUT_S):
        """{rank: (payload|None, error|None)} — one thread per rank, each
        joined against the shared deadline; a thread still running past
        it is reported as a timeout and LEFT BEHIND (daemon), so the
        slowest rank bounds the response time, never blocks it."""
        results = {}
        threads = []
        for rank in range(self.world):
            t = threading.Thread(
                target=lambda r=rank: results.__setitem__(
                    r, self._fetch(r, path, timeout)),
                daemon=True)
            t.start()
            threads.append(t)
        deadline = time.monotonic() + timeout + 1.0
        for t in threads:
            t.join(timeout=max(0.05, deadline - time.monotonic()))
        return {r: results.get(r, (None, f"timeout after {timeout}s"))
                for r in range(self.world)}

    # -- merged views ----------------------------------------------------
    def merged_healthz(self):
        out = {"ok": True, "world_size": self.world,
               "generation": self.generation, "ts": time.time(),
               "aggregator": True, "ranks": {}, "unreachable_ranks": [],
               "failing_ranks": []}
        for rank, (payload, err) in sorted(self.fan_out("/healthz").items()):
            if payload is None:
                out["ranks"][str(rank)] = {"error": err}
                out["unreachable_ranks"].append(rank)
                out["ok"] = False
            elif payload.get("http_status", 0) >= 400:
                # the rank answered, but with an ERROR verdict (older
                # build without the endpoint, persistent 500): reachable
                # yet broken — it must still fail the gang health
                out["ranks"][str(rank)] = payload
                out["failing_ranks"].append(rank)
                out["ok"] = False
            else:
                out["ranks"][str(rank)] = payload
        return out

    def merged_statusz(self, stale_after=None):
        """The merged gang view. `stale_after=None` (the default) uses
        the SCOPE_STALE_AFTER_S floor scaled by the gang's fastest
        reported step rate — a healthy 6 s/step gang must not read
        all-STALE between boundaries, while ~5 step intervals of
        silence is suspicious at any cadence (a gang-wide wedge freezes
        each rank's rate window at its healthy positive value, so the
        scaled threshold stays honest there too). An EXPLICIT value
        (?stale_after=S) is used exactly as given — an operator's
        threshold is never silently out-scaled."""
        explicit = stale_after is not None
        floor = float(stale_after) if explicit else SCOPE_STALE_AFTER_S
        out = {"world_size": self.world, "generation": self.generation,
               "ts": time.time(), "aggregator": True,
               "stale_after_s": floor, "ranks": {}, "stale_ranks": [],
               "unreachable_ranks": [], "failing_ranks": []}
        fetched = sorted(self.fan_out("/statusz").items())
        effective = floor
        if not explicit:
            rates = [p["steps_per_s"] for _r, (p, _e) in fetched
                     if p and isinstance(p.get("steps_per_s"),
                                         (int, float))
                     and p["steps_per_s"] > 0]
            if rates:
                effective = max(floor, 5.0 / max(rates))
        out["stale_after_effective_s"] = round(effective, 3)
        steps = []
        for rank, (payload, err) in fetched:
            if payload is None:
                out["ranks"][str(rank)] = {"error": err}
                out["unreachable_ranks"].append(rank)
                continue
            out["ranks"][str(rank)] = payload
            if payload.get("http_status", 0) >= 400:
                # answered with an error verdict: reachable but broken
                out["failing_ranks"].append(rank)
                continue
            if payload.get("step") is not None:
                steps.append(int(payload["step"]))
            # a rank that answers but stopped completing steps (wedged
            # collective, dead input) is STALE: the hung main thread
            # cannot advance `step`, while the scope server thread —
            # like the hung rank's heartbeat file — keeps answering
            ages = [a for a in (payload.get("last_step_age_s"),
                                payload.get("heartbeat_age_s"))
                    if isinstance(a, (int, float))]
            if ages and max(ages) > effective:
                out["stale_ranks"].append(rank)
        if steps:
            out["max_step"] = max(steps)
            out["min_step"] = min(steps)
            out["step_spread"] = max(steps) - min(steps)
        return out

    def merged_metrics(self):
        """Gang-level exposition the base port can serve without merging
        N identical per-rank registries: reachability, last step, and
        ages, one labeled sample per rank."""
        status = self.merged_statusz()
        lines = [
            "# HELP scope_rank_reachable per-rank mx.scope endpoint "
            "answered the aggregator fan-out",
            "# TYPE scope_rank_reachable gauge",
        ]
        for rank in range(self.world):
            reachable = rank not in status["unreachable_ranks"]
            lines.append(f'scope_rank_reachable{{rank="{rank}"}} '
                         f"{int(reachable)}")
        lines += ["# TYPE scope_rank_step gauge",
                  "# TYPE scope_rank_step_age_seconds gauge"]
        for rank in range(self.world):
            p = status["ranks"].get(str(rank)) or {}
            if isinstance(p.get("step"), int):
                lines.append(f'scope_rank_step{{rank="{rank}"}} '
                             f"{p['step']}")
            if isinstance(p.get("last_step_age_s"), (int, float)):
                lines.append(
                    f'scope_rank_step_age_seconds{{rank="{rank}"}} '
                    f"{p['last_step_age_s']}")
        lines.append(f"scope_gang_stale_ranks {len(status['stale_ranks'])}")
        lines.append("scope_gang_unreachable_ranks "
                     f"{len(status['unreachable_ranks'])}")
        lines.append("scope_gang_failing_ranks "
                     f"{len(status['failing_ranks'])}")
        return "\n".join(lines) + "\n"

    def proxy_profilez(self, query):
        """Arm a device capture on EVERY rank at once. The per-rank wait
        budget follows the request's wait_s (a capture legitimately
        spans steps) plus a margin; each rank still answers 202
        immediately when wait_s=0."""
        q = parse_qs(query)
        try:
            wait_s = float(q.get("wait_s", ["60"])[0])
            if "steps" in q:
                int(q["steps"][0])
        except ValueError:
            # fail the whole request up front: fanning a malformed query
            # out would collect N per-rank 400s under an aggregator 200,
            # and a script gating on status would believe a gang capture
            # started (the handler maps this to HTTP 400)
            raise ValueError(
                "malformed profilez query: steps/wait_s must be numeric")
        path = "/profilez" + (f"?{query}" if query else "")
        results = self.fan_out(path, timeout=max(wait_s, 1.0) + 5.0)
        out = {"world_size": self.world, "aggregator": True,
               "ranks": {}, "unreachable_ranks": []}
        for rank, (payload, err) in sorted(results.items()):
            if payload is None:
                out["ranks"][str(rank)] = {"error": err}
                out["unreachable_ranks"].append(rank)
            else:
                out["ranks"][str(rank)] = payload
        return out

    # -- http ------------------------------------------------------------
    def _make_handler(self):
        agg = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _send(self, code, payload,
                      content_type="application/json"):
                body = payload if isinstance(payload, bytes) else \
                    json.dumps(payload, default=str).encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                parts = urlsplit(self.path)
                route = parts.path.rstrip("/") or "/"
                q = parse_qs(parts.query)
                try:
                    if route == "/healthz":
                        self._send(200, agg.merged_healthz())
                    elif route == "/statusz":
                        stale = q.get("stale_after")
                        self._send(200, agg.merged_statusz(
                            float(stale[0]) if stale else None))
                    elif route == "/metrics":
                        self._send(200, agg.merged_metrics().encode(),
                                   content_type="text/plain; "
                                   "version=0.0.4; charset=utf-8")
                    elif route == "/profilez":
                        self._send(200, agg.proxy_profilez(parts.query))
                    elif route == "/":
                        self._send(200, {
                            "aggregator": True,
                            "world_size": agg.world,
                            "rank_ports": {
                                r: agg.base_port + 1 + r
                                for r in range(agg.world)},
                            "endpoints": ["/healthz", "/statusz",
                                          "/metrics",
                                          "/profilez?steps=N"]})
                    else:
                        self._send(404, {
                            "error": f"no such endpoint {route!r}"})
                except BrokenPipeError:
                    pass
                except ValueError as e:
                    # malformed query values (stale_after=abc): client
                    # error, not an aggregator fault
                    try:
                        self._send(400, {"error": str(e)})
                    except OSError:
                        pass
                except Exception as e:  # noqa: BLE001
                    try:
                        self._send(500, {
                            "error": f"{type(e).__name__}: {e}"})
                    except OSError:
                        pass

        return Handler


def _start_scope_aggregator(scope_port, world, generation):
    """Best-effort aggregator construction: introspection must never
    kill the gang it observes (a taken base port degrades to per-rank
    scraping with a warning)."""
    if not scope_port:
        return None
    try:
        return _ScopeAggregator(scope_port, world, generation)
    except OSError as e:
        print(f"launch: cannot start the mx.scope aggregator on port "
              f"{scope_port}: {e} — per-rank endpoints "
              f"({scope_port + 1}..{scope_port + world}) still serve",
              file=sys.stderr)
        return None


def _plan_world(world, codes, elastic, min_workers, max_world):
    """Decide the next generation's world size from one failed
    generation's exit-code snapshot (taken BEFORE teardown, so a rank's
    code reflects how IT died, not the supervisor's SIGTERM).

      * not elastic → same world (the pre-elastic behavior).
      * every observed failure is EXIT_GROW → grow by one, capped at the
        original -n (capacity came back; the gang reabsorbs it).
      * ranks lost their SLOT — EXIT_SHRINK, a graceful preemption
        (EXIT_PREEMPTED), or an eviction kill (SIGKILL/SIGTERM from the
        scheduler) — → the surviving world size, floored at
        --min-workers: preemption on a shrinking pod is a reshape, not a
        failure.
      * plain crashes — nonzero exit codes AND crash signals
        (SIGSEGV/SIGABRT/...) — → same world: a reproducible code bug
        must not shrink the gang one worker per restart until nothing is
        left.

    Returns (new_world, surviving_ranks, lost_ranks)."""
    failed = {r: c for r, c in enumerate(codes) if c not in (None, 0)}
    surviving = [r for r in range(world) if r not in failed]
    if not elastic:
        return world, surviving, sorted(failed)
    if failed and all(c == EXIT_GROW for c in failed.values()):
        return min(max_world, world + 1), surviving, []
    slot_loss = (-signal.SIGKILL, -signal.SIGTERM,
                 EXIT_SHRINK, EXIT_PREEMPTED)
    lost = sorted(r for r, c in failed.items() if c in slot_loss)
    if lost:
        return max(min_workers, world - len(lost)), surviving, lost
    return world, surviving, sorted(failed)


def launch_local(num_workers, command, coordinator, diagnostics_dir=None,
                 max_restarts=0, restart_backoff=3.0, elastic=False,
                 min_workers=1, trace_dir=None, heartbeat_timeout=0.0,
                 scope_port=0, goodput_dir=None):
    """Run the gang; with --max-restarts, supervise it: when any rank
    dies (crash, SIGKILL rank death, or a preemption save), tear down the
    peer ranks, back off exponentially (with jitter), and relaunch the
    whole gang — which auto-resumes from the last good checkpoint when
    the workers run with mx.resilience + resume='auto'. With --elastic
    the relaunch happens at the SURVIVING world size (see _plan_world):
    workers resuming with reshard='auto' redistribute the checkpoint onto
    the new topology, so losing devices no longer loses the run."""
    killed = {}

    def _kill(signum, _frame):
        # flag only (async-signal-safe): the reap loop forwards the
        # ACTUAL signal so preemption-aware workers save, reaps the
        # children (no zombies), and flushes/closes the worker.log
        # tee pumps before exiting 128+signum
        killed["sig"] = signum

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    attempt = 0
    world = num_workers
    trace_epoch_ns = time.time_ns() if (trace_dir or goodput_dir) else None
    while True:
        if killed.get("sig"):
            # signal arrived during the restart backoff: no gang running,
            # nothing to tear down — just exit with the signal code
            sys.exit(128 + killed["sig"])
        procs, pumps = [], []
        for rank in range(world):
            env = build_env(rank, world, coordinator, diagnostics_dir,
                            restart_count=attempt, trace_dir=trace_dir,
                            trace_epoch_ns=trace_epoch_ns,
                            heartbeat_timeout=heartbeat_timeout,
                            scope_port=scope_port,
                            goodput_dir=goodput_dir)
            proc, pump = _spawn(command, env, rank, diagnostics_dir,
                                restart_count=attempt)
            procs.append(proc)
            pumps.append(pump)
        # gang introspection aggregator for THIS generation (the world
        # size can change across elastic relaunches, so it is rebuilt
        # per generation like the heartbeat monitor)
        aggregator = _start_scope_aggregator(scope_port, world, attempt)
        monitor = None
        if heartbeat_timeout and diagnostics_dir:
            # liveness poll for THIS generation: a rank whose mx.guard
            # heartbeat goes stale is SIGKILLed (slot loss), so a hung
            # collective resolves into a relaunch instead of an
            # indefinite stall
            monitor = _HeartbeatMonitor(procs, diagnostics_dir,
                                        heartbeat_timeout, attempt)
        # the heartbeat monitor implies early-exit even without
        # --max-restarts: its SIGKILL of a stuck rank leaves the peers
        # blocked in the dead collective, so waiting for ALL ranks would
        # turn the detected hang into a permanent launcher hang — reap
        # the first death, tear the gang down, and exit with the code
        code, rank = _reap(procs, pumps,
                           early_exit=max_restarts > 0 or monitor is not None,
                           killed=killed)
        codes = [p.poll() for p in procs]
        if code != 0 and (max_restarts > 0 or monitor is not None):
            if elastic:
                # settle window: let co-failing ranks (several workers of
                # one evicted slice) finish dying before the snapshot, so
                # the shrink happens once, not one worker per relaunch
                deadline = time.monotonic() + ELASTIC_SETTLE_S
                while time.monotonic() < deadline \
                        and any(p.poll() is None for p in procs) \
                        and not killed.get("sig"):
                    time.sleep(0.05)
                codes = [p.poll() for p in procs]
            # early-exit reap leaves the peers running: tear the gang down
            # whether or not a relaunch follows (no orphans on giving up)
            _terminate_gang(procs, pumps)
        if monitor is not None:
            monitor.stop()
        if aggregator is not None:
            aggregator.stop()
        if code == 0 or attempt >= max_restarts:
            return code
        new_world, surviving, lost = _plan_world(
            world, codes, elastic, min_workers, num_workers)
        # EXIT_PEER_LOST inverts the usual attribution: the exiting rank
        # is the HEALTHY reporter, and the actually-dead peer is still
        # wedged (no exit code) at snapshot time — it only dies to the
        # teardown SIGKILL, which the pre-teardown snapshot can never
        # see. Prefer the reporter's own post-mortem evidence (its guard
        # section names the suspect from heartbeat ages): in gangs >2 the
        # OTHER still-running ranks are healthy peers whose deadlines
        # simply haven't fired yet, not dead ones — so when no reporter
        # post-mortem names a suspect (guard dir unwritable, heartbeat
        # evidence missing), the suspicion stays EMPTY rather than
        # smearing every running rank. Record both sides so
        # restarts.jsonl doesn't list the dead peer as a survivor.
        reporters = [r for r, c in enumerate(codes) if c == EXIT_PEER_LOST]
        suspected = []
        if reporters:
            running = [r for r, c in enumerate(codes) if c is None]
            named = set()
            for rr in reporters:
                try:
                    with open(os.path.join(diagnostics_dir, str(rr),
                                           "postmortem.json")) as f:
                        pm = json.load(f)
                    s = (((pm.get("guard") or {}).get("peer_lost") or {})
                         .get("suspect") or {})
                    if s.get("rank") is not None:
                        named.add(int(s["rank"]))
                except (OSError, TypeError, ValueError):
                    continue
            suspected = sorted(named & set(running))
        attempt += 1
        backoff = restart_backoff * (2.0 ** (attempt - 1)) \
            * random.uniform(0.8, 1.2)
        _log_restart(diagnostics_dir, {
            "ts": time.time(), "kind": "restart", "attempt": attempt,
            "failed_rank": rank, "exit_code": code,
            "preempted": code == EXIT_PREEMPTED,
            "world_size": world, "new_world_size": new_world,
            "surviving_ranks": [r for r in surviving
                                if r not in suspected],
            "lost_ranks": lost,
            "peer_lost_reporters": reporters,
            "suspected_dead_ranks": suspected,
            "elastic": bool(elastic),
            "backoff_s": round(backoff, 3)})
        world = new_world
        # sliced sleep: PEP 475 restarts a plain sleep after the flag-only
        # signal handler runs, so a Ctrl-C during a long backoff would
        # otherwise be ignored until the backoff elapsed
        end = time.monotonic() + backoff
        while time.monotonic() < end and not killed.get("sig"):
            time.sleep(min(0.2, max(0.0, end - time.monotonic())))


def _load_fleet():
    """Load the stdlib-only router half of mxnet_tpu/fleet.py by path —
    the launcher must stay import-light (no jax, no package import),
    same pattern as _load_locklint."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "mxnet_tpu", "fleet.py")
    spec = importlib.util.spec_from_file_location("mx_fleet", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def launch_fleet(num_replicas, command, coordinator, diagnostics_dir=None,
                 max_restarts=0, restart_backoff=3.0, min_workers=1,
                 max_replicas=0, fleet_port=8900, heartbeat_timeout=0.0,
                 scope_port=0):
    """The serving gang (--serve-replicas N): N replica worker processes
    (each one `serve.Server` + fleet endpoint on fleet_port+1+R, plus
    its mx.scope endpoints when armed) behind the fleet router's front
    door on fleet_port. Unlike the training gang, replicas are
    INDEPENDENT — a dead replica is relaunched alone while the router
    fails its in-flight requests over to survivors; nothing tears the
    gang down. SIGTERM to the launcher drains every replica (zero-drop:
    each stops admitting, finishes or requeues in-flight work, exits
    via the resilience preemption path) before the router stops.

    `POST /roll {"version": v}` on the front door rolls the fleet
    replica-by-replica onto new weights; queue-wait autoscale (the
    fleet_autoscale knob) resizes the replica count between
    --min-workers and --serve-replicas-max, clamped through the same
    _plan_world step the elastic training gang uses."""
    fleet = _load_fleet()
    max_replicas = max_replicas or num_replicas
    killed = {}
    signal.signal(signal.SIGINT, lambda s, f: killed.setdefault("sig", s))
    signal.signal(signal.SIGTERM, lambda s, f: killed.setdefault("sig", s))

    version = os.environ.get("MXNET_TPU_FLEET_VERSION", "v0")
    command = list(command) or [sys.executable, "-m", "mxnet_tpu.fleet"]
    replicas = {}        # rid -> {"proc", "pump", "restarts", "ver"}

    def _replica_url(rid):
        return f"http://127.0.0.1:{fleet_port + 1 + rid}"

    def _spawn_replica(rid, restart_count, ver):
        env = build_env(rid, max_replicas, coordinator, diagnostics_dir,
                        restart_count=restart_count,
                        heartbeat_timeout=heartbeat_timeout,
                        scope_port=scope_port)
        env["MXNET_TPU_FLEET_REPLICA"] = str(rid)
        env["MXNET_TPU_FLEET_PORT"] = str(fleet_port + 1 + rid)
        env["MXNET_TPU_FLEET_VERSION"] = ver
        proc, pump = _spawn(command, env, rid, diagnostics_dir,
                            restart_count=restart_count)
        replicas[rid] = {"proc": proc, "pump": pump,
                         "restarts": restart_count, "ver": ver}
        return proc

    for rid in range(num_replicas):
        _spawn_replica(rid, 0, version)

    router = fleet.Router({rid: _replica_url(rid) for rid in replicas})
    router.start()
    front = fleet.RouterServer(router, fleet_port)
    print(f"launch: fleet front door on {front.url} "
          f"({num_replicas} replica(s), ports "
          f"{fleet_port + 1}..{fleet_port + num_replicas})", flush=True)
    # gang introspection over the REPLICA ids (replicas restart
    # independently, so the merged view spans whatever incarnation each
    # id is on — generation pins to 0)
    aggregator = _start_scope_aggregator(scope_port, max_replicas, 0)

    target = [num_replicas]
    roll_req = []

    def _on_scale(n):
        # clamp the autoscaler's ask through the elastic world-size
        # plumbing: one _plan_world step per direction, never a jump
        cur = target[0]
        while n != cur:
            codes = [None] * max(cur, 1)
            codes[-1] = EXIT_GROW if n > cur else EXIT_SHRINK
            nxt, _, _ = _plan_world(max(cur, 1), codes, True,
                                    min_workers, max_replicas)
            if nxt == cur:
                break
            cur = nxt
        if cur != target[0]:
            print(f"launch: fleet scale {target[0]} -> {cur}", flush=True)
            target[0] = cur

    router.on_scale = _on_scale
    front.on_scale = _on_scale
    front.on_roll = lambda ver: roll_req.append(ver or version)

    # one liveness monitor PER replica incarnation (not per gang): each
    # replica restarts independently, so its heartbeat generation is its
    # own restart count — a gang-wide monitor generation would match at
    # most one replica. The procs list is padded with already-dead
    # placeholders so the monitor's rank indexing (rank R reads
    # <dir>/R/heartbeat.json) lines up with the replica id.
    class _DeadProc:
        def poll(self):
            return 0

    monitors = {}

    def _remonitor(rid):
        old = monitors.pop(rid, None)
        if old is not None:
            old.stop()
        if heartbeat_timeout and diagnostics_dir and rid in replicas:
            procs = [_DeadProc()] * rid + [replicas[rid]["proc"]]
            monitors[rid] = _HeartbeatMonitor(
                procs, diagnostics_dir, heartbeat_timeout,
                replicas[rid]["restarts"])

    for rid in sorted(replicas):
        _remonitor(rid)
    exit_code = 0
    try:
        while not killed.get("sig"):
            time.sleep(0.2)
            # -- reap & relaunch dead replicas (independently) ---------
            for rid, st in sorted(replicas.items()):
                code = st["proc"].poll()
                if code is None:
                    continue
                _append_restart_event(diagnostics_dir, {
                    "ts": time.time(), "kind": "replica_exit",
                    "replica": rid, "exit_code": code,
                    "preempted": code == EXIT_PREEMPTED,
                    "restarts": st["restarts"]})
                if rid >= target[0]:
                    # retired by scale-down: drained, do not relaunch
                    del replicas[rid]
                    router.remove_replica(rid)
                    _remonitor(rid)
                    continue
                if st["restarts"] >= max_restarts:
                    print(f"launch: replica {rid} exited {code} with no "
                          f"restart budget left — removing from fleet",
                          file=sys.stderr, flush=True)
                    del replicas[rid]
                    router.remove_replica(rid)
                    _remonitor(rid)
                    if not replicas:
                        exit_code = code if code else 1
                        raise KeyboardInterrupt
                    continue
                backoff = restart_backoff * random.uniform(0.8, 1.2)
                print(f"launch: replica {rid} exited {code} — relaunching "
                      f"in {backoff:.1f}s (router fails its in-flight "
                      "requests over to survivors)", flush=True)
                end = time.monotonic() + backoff
                while time.monotonic() < end and not killed.get("sig"):
                    time.sleep(0.05)
                _spawn_replica(rid, st["restarts"] + 1, st["ver"])
                _append_restart_event(diagnostics_dir, {
                    "ts": time.time(), "kind": "replica_relaunch",
                    "replica": rid, "attempt": st["restarts"] + 1,
                    "exit_code": code,
                    "preempted": code == EXIT_PREEMPTED})
                _remonitor(rid)
            # -- reconcile autoscale target ----------------------------
            live = sorted(replicas)
            if len(live) < target[0]:
                rid = next(i for i in range(max_replicas)
                           if i not in replicas)
                print(f"launch: fleet grow — spawning replica {rid}",
                      flush=True)
                _spawn_replica(rid, 0, version)
                router.add_replica(rid, _replica_url(rid))
                _remonitor(rid)
            elif len(live) > target[0]:
                rid = live[-1]
                print(f"launch: fleet shrink — draining replica {rid}",
                      flush=True)
                router.drain(rid)
                try:
                    replicas[rid]["proc"].send_signal(signal.SIGTERM)
                except OSError:
                    pass
            # -- rolling update ----------------------------------------
            if roll_req:
                ver = roll_req.pop(0)
                print(f"launch: rolling update -> {ver}", flush=True)
                for rid in sorted(replicas):
                    if killed.get("sig"):
                        break
                    router.drain(rid)
                    router.wait_idle(rid, timeout_s=60.0)
                    st = replicas[rid]
                    try:
                        st["proc"].send_signal(signal.SIGTERM)
                    except OSError:
                        pass
                    try:
                        st["proc"].wait(timeout=60.0)
                    except subprocess.TimeoutExpired:
                        st["proc"].kill()
                        st["proc"].wait()
                    code = st["proc"].poll()
                    _append_restart_event(diagnostics_dir, {
                        "ts": time.time(), "kind": "replica_roll",
                        "replica": rid, "exit_code": code,
                        "version": ver})
                    _spawn_replica(rid, st["restarts"] + 1, ver)
                    router.undrain(rid, remote=False)
                    router.wait_healthy(rid, timeout_s=120.0, version=ver)
                    _remonitor(rid)
                version = ver
                print(f"launch: rolling update to {ver} complete",
                      flush=True)
    except KeyboardInterrupt:
        pass
    finally:
        for mon in monitors.values():
            mon.stop()
        # zero-drop teardown: SIGTERM tells every replica to drain
        # (finish or requeue in-flight work) before _terminate_gang's
        # grace expires
        procs = [st["proc"] for st in replicas.values()]
        pumps = [st["pump"] for st in replicas.values()]
        _terminate_gang(procs, pumps, grace=30.0)
        if aggregator is not None:
            aggregator.stop()
        front.stop()
        router.stop()
    sig = killed.get("sig")
    return exit_code if sig is None else 128 + sig


def launch_ssh(hosts, num_workers, command, coordinator, username=None,
               diagnostics_dir=None, trace_dir=None):
    procs, pumps = [], []
    trace_epoch_ns = time.time_ns() if trace_dir else None
    for rank in range(num_workers):
        host = hosts[rank % len(hosts)]
        target = f"{username}@{host}" if username else host
        env = build_env(rank, num_workers, coordinator, diagnostics_dir,
                        trace_dir=trace_dir, trace_epoch_ns=trace_epoch_ns)
        exports = " ".join(
            f"{k}={v!r}" for k, v in env.items()
            if k.startswith(("JAX_", "DMLC_", "MXNET_TPU_")))
        remote_cmd = f"cd {os.getcwd()!r} && env {exports} " + \
            " ".join(command)
        # the per-rank worker.log tees the ssh-forwarded output on THIS
        # host; the remote-side postmortem.json still lands on the remote
        # filesystem (collect with scp before merging)
        proc, pump = _spawn(
            [remote_cmd], env, rank, diagnostics_dir,
            extra_args=["ssh", "-o", "StrictHostKeyChecking=no", target])
        procs.append(proc)
        pumps.append(pump)
    code, _rank = _reap(procs, pumps)
    return code


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("-n", "--num-workers", type=int, default=0)
    p.add_argument("-s", "--num-servers", type=int, default=0,
                   help="ignored: no parameter servers on TPU")
    p.add_argument("-H", "--hostfile", default=None,
                   help="file with one host per line (ssh launcher)")
    p.add_argument("--launcher", choices=["local", "ssh"], default="local")
    p.add_argument("--coordinator", default="127.0.0.1:9876",
                   help="host:port for jax.distributed coordination")
    p.add_argument("--username", default=None)
    p.add_argument("--diagnostics-dir", default=None,
                   help="arm mx.diagnostics in every worker and tee each "
                        "worker's output to <dir>/<rank>/worker.log; "
                        "crashes leave <dir>/<rank>/postmortem.json")
    p.add_argument("--trace-dir", default=None,
                   help="arm mx.trace in every worker (MXNET_TPU_TRACE=on)"
                        ": each rank appends sampled step/input/compile/"
                        "checkpoint spans and skew probes to "
                        "<dir>/<rank>/trace.jsonl against one shared gang "
                        "trace epoch; merge into a clock-aligned Perfetto "
                        "trace + straggler verdict with "
                        "tools/trace_report.py")
    p.add_argument("--goodput-dir", default=None,
                   help="arm mx.goodput wall-clock accounting in every "
                        "worker (MXNET_TPU_GOODPUT=on): each rank appends "
                        "classified goodput/badput intervals (step, "
                        "compile, input stall, checkpoint, reshard, OOM "
                        "recovery, replay, serve decode/idle/degraded) to "
                        "<dir>/<rank>/goodput.jsonl against the shared "
                        "gang epoch; merge with restarts.jsonl into a "
                        "gang accounting table and verdict with "
                        "tools/goodput_report.py")
    p.add_argument("--heartbeat-timeout", type=float, default=0.0,
                   help="arm mx.guard liveness in every worker "
                        "(MXNET_TPU_GUARD=1) and poll the per-rank "
                        "heartbeat files under --diagnostics-dir: a rank "
                        "whose beat goes stale for more than this many "
                        "seconds is SIGKILLed (a stuck-but-alive hang "
                        "becomes a slot loss, which --elastic relaunches "
                        "at the surviving world size). 0 (default) "
                        "disables. Explicit flag only — the "
                        "MXNET_TPU_HEARTBEAT_TIMEOUT_S env var is the "
                        "WORKER-side staleness knob (this flag exports "
                        "it), and its presence alone must not arm "
                        "supervisor kills.")
    p.add_argument("--scope-port", type=int, default=0,
                   help="arm mx.scope live introspection in every worker "
                        "(MXNET_TPU_SCOPE=on): rank R serves /healthz "
                        "/metrics /statusz /tracez /profilez on port "
                        "P+1+R, and the launcher runs a gang aggregator "
                        "on the base port P that merges /statusz into "
                        "one gang view (stale/unreachable ranks named) "
                        "and proxies /profilez to every rank at once — "
                        "watch it live with tools/scope_top.py. 0 "
                        "(default) disables.")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="supervised relaunch (local launcher): when any "
                        "rank exits nonzero, tear down the peers, back "
                        "off, and relaunch the whole gang up to N times "
                        "(workers see MXNET_TPU_RESTART_COUNT; with "
                        "mx.resilience + resume=auto they resume from "
                        "the last good checkpoint)")
    p.add_argument("--restart-backoff", type=float, default=3.0,
                   help="base seconds between relaunches; doubles per "
                        "restart, jittered +-20%%")
    p.add_argument("--elastic", action="store_true",
                   default=os.environ.get("MXNET_TPU_ELASTIC", "").lower()
                   in ("1", "true", "yes", "on"),
                   help="elastic gang (with --max-restarts): relaunch at "
                        "the SURVIVING world size when ranks lose their "
                        "slot (signal death, preemption save, or an "
                        "injected shrink request), grow back one worker "
                        "on an EXIT_GROW request (capped at -n). Workers "
                        "resuming with mx.resilience reshard='auto' "
                        "redistribute the checkpoint onto the new "
                        "topology. Default from MXNET_TPU_ELASTIC.")
    p.add_argument("--min-workers", type=int,
                   default=int(os.environ.get("MXNET_TPU_MIN_WORKERS",
                                              "1")),
                   help="smallest world size an elastic gang may shrink "
                        "to: a relaunch after slot losses is clamped to "
                        "this floor, never below it. Default from "
                        "MXNET_TPU_MIN_WORKERS.")
    p.add_argument("--serve-replicas", type=int, default=0,
                   help="fleet serving mode (local launcher): spawn N "
                        "replica worker processes (default command: "
                        "python -m mxnet_tpu.fleet), each one serve.Server "
                        "with a fleet endpoint on --fleet-port+1+R, and "
                        "run the health-routed front door on --fleet-port. "
                        "Replicas are supervised INDEPENDENTLY: a dead "
                        "replica is relaunched alone (restarts.jsonl "
                        "records replica_exit/replica_relaunch) while the "
                        "router fails its in-flight requests over to "
                        "survivors with bit-identical replay. SIGTERM "
                        "drains every replica (zero-drop) before exit; "
                        "POST /roll on the front door rolls the fleet "
                        "replica-by-replica onto new weights.")
    p.add_argument("--fleet-port", type=int,
                   default=int(os.environ.get("MXNET_TPU_FLEET_PORT_BASE",
                                              "8900")),
                   help="front-door port for --serve-replicas; replica R "
                        "listens on this port +1+R (same layout as "
                        "--scope-port)")
    p.add_argument("--max-replicas", type=int, default=0,
                   help="ceiling for fleet queue-wait autoscale "
                        "(MXNET_TPU_FLEET_AUTOSCALE=on): sustained p99 "
                        "queue wait grows the fleet one replica at a time "
                        "up to this cap, quiet periods shrink it back "
                        "toward --min-workers — each resize clamped "
                        "through the same elastic world-size step the "
                        "training gang uses. Default: --serve-replicas "
                        "(autoscale can only shrink).")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    if not args.serve_replicas and args.num_workers <= 0:
        p.error("one of -n/--num-workers or --serve-replicas is required")
    if not args.command and not args.serve_replicas:
        p.error("no command given")
    if args.num_servers:
        print("warning: -s/--num-servers ignored — TPU SPMD has no "
              "parameter servers; gradients reduce via XLA collectives",
              file=sys.stderr)

    if args.heartbeat_timeout and not args.diagnostics_dir:
        p.error("--heartbeat-timeout needs --diagnostics-dir (the "
                "heartbeat files live under it)")

    if args.serve_replicas:
        if args.launcher != "local":
            p.error("--serve-replicas is local-launcher only")
        return launch_fleet(args.serve_replicas, args.command,
                            args.coordinator, args.diagnostics_dir,
                            max_restarts=args.max_restarts,
                            restart_backoff=args.restart_backoff,
                            min_workers=args.min_workers,
                            max_replicas=args.max_replicas,
                            fleet_port=args.fleet_port,
                            heartbeat_timeout=args.heartbeat_timeout,
                            scope_port=args.scope_port)

    if args.launcher == "ssh":
        if not args.hostfile:
            p.error("ssh launcher needs -H hostfile")
        if args.max_restarts or args.elastic:
            print("warning: --max-restarts/--elastic are local-launcher "
                  "only (supervise ssh gangs externally)", file=sys.stderr)
        if args.heartbeat_timeout:
            print("warning: --heartbeat-timeout is local-launcher only "
                  "(remote heartbeat files are not visible here)",
                  file=sys.stderr)
        if args.goodput_dir:
            print("warning: --goodput-dir is local-launcher only (arm "
                  "remote workers with MXNET_TPU_GOODPUT=on / "
                  "MXNET_TPU_GOODPUT_DIR and collect the rank files "
                  "before running tools/goodput_report.py)",
                  file=sys.stderr)
        if args.scope_port:
            print("warning: --scope-port is local-launcher only (the "
                  "aggregator fans out to 127.0.0.1 rank ports; arm "
                  "remote workers with MXNET_TPU_SCOPE=on and scrape "
                  "them directly)", file=sys.stderr)
        with open(args.hostfile) as f:
            hosts = [line.strip() for line in f if line.strip()]
        return launch_ssh(hosts, args.num_workers, args.command,
                          args.coordinator, args.username,
                          args.diagnostics_dir, trace_dir=args.trace_dir)
    return launch_local(args.num_workers, args.command, args.coordinator,
                        args.diagnostics_dir,
                        max_restarts=args.max_restarts,
                        restart_backoff=args.restart_backoff,
                        elastic=args.elastic,
                        min_workers=args.min_workers,
                        trace_dir=args.trace_dir,
                        heartbeat_timeout=args.heartbeat_timeout,
                        scope_port=args.scope_port,
                        goodput_dir=args.goodput_dir)


if __name__ == "__main__":
    sys.exit(main())
