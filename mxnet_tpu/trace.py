"""mx.trace — the program's own spans and scopes, on the device trace's
clock, with cross-rank straggler and critical-path attribution.

A device trace says what the chip did and when; only the program can say
what the host was doing meanwhile and which part of the model an
anonymous `copy.602` belongs to. This module is both halves:

  * **live spans** — `span(name, **attrs)` at the phase boundaries of
    `Server.step` (`serve.step` ⊃ `serve.schedule`, `serve.prepare`,
    `serve.decode_step` ⊃ `serve.fetch`, `serve.stream`; per request
    `serve.queue_wait`, `serve.admit`), of `ShardedTrainer.step_async`
    (`train.step` ⊃ `step.dispatch`; `step.compile` on a miss;
    `step.fence` where another observer fenced), of the prefetcher
    (`input.batch_wait`, `input.h2d_stage`), plus `compile` and
    `checkpoint.save`. A live span enters a
    `jax.profiler.TraceAnnotation` of the same name, so it is in the
    profiler's capture on the device trace's clock, and appends to a
    bounded buffer (`spans()`), tagged `(rank, step)`, for every
    `trace_sample_every`-th step. docs/trace.md has the table.
  * **device scopes** — `jax.named_scope`s inside the jitted steps
    (`kv_arena_update`, `page_gather`, `lm_head`; `forward`, whose
    backward autodiff names `transpose(jvp(forward))`, `grad_reduce`,
    `optimizer`). `note_executable()` remembers each step executable at
    compile time and `scope_map()` reads, on demand, which HLO
    instruction was traced under which scope: the join from a device
    trace's event names to names a refactor keeps.
  * **set-up table** — `setup()`: always-on sums of where the seconds
    before the first step went (import, `initialize()`, jit misses,
    compile-cache hits and misses).
  * **skew probe** (under `enable()` only) — every `trace_skew_every`
    sampled steps, each rank wall-stamps its arrival at the collective
    boundary (a tiny timestamped all-gather when jax runs
    multi-process), feeding the `step_skew_seconds` / `straggler_rank`
    telemetry gauges, a flight-ring "trace" entry, and the post-mortem
    "trace" section.
  * **per-rank span files** (under `enable()` with `trace_dir`) — spans
    append to `<dir>/<rank>/trace.jsonl` behind a meta line carrying
    this rank's wall-clock epoch (and the gang epoch tools/launch.py
    --trace-dir exports), so `tools/trace_report.py` can merge all ranks
    into one clock-aligned Perfetto/chrome trace and print a measured
    gang-wide verdict: input-bound / compute-bound / comm-skew-bound,
    naming the straggler rank and its dominant span.

Clock model: the buffer's spans timestamp against the process-wide
monotonic epoch in `mxnet_tpu.util` — the SAME epoch mx.profiler's chrome
events and telemetry's event mirror use — and the meta line maps that
epoch to wall time, so merged multi-rank timelines align without per-file
clock math. The TraceAnnotation half of a span is stamped by the profiler
itself; a reader joins the two clocks through a span that encloses, or
lies in, an event it knows on the profiler's clock.

Cost model. `live()` is `enable()`d OR a `jax.profiler` session is
recording: start the profiler and the program's spans are in the
capture; nothing else to switch on. NOT live (the default) is the
production fast path: `Server.step`, `step_async` and the prefetcher read
`live()` once a step (two bools) into a local, every span site is
`with (span(...) if tr else _NULLCTX):`, and nothing else runs — no span
object, no TraceAnnotation, no buffer, no lock, no clock read
(`ci/run.sh sanity` and tests/unittest/test_trace.py count the calls).
Live, a span costs two clock reads, one TraceAnnotation and one locked
append: microseconds. A live span NEVER fences, fetches or waits: device
time is the device trace's to give, and a fence per traced step would
serialise the very prefetch/dispatch overlap the trace is there to show.
Spans armed by a profiler session alone stay in memory; files, the skew
probe and sampling strides need `mx.trace.enable()` /
`MXNET_TPU_TRACE=on` / `tools/launch.py --trace-dir`.
"""
from __future__ import annotations

import atexit
import json
import os
import re
import sys
import time

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation as _TraceMe

from . import _locklint
from . import config as _config
from . import telemetry as _telemetry
from . import util as _util

__all__ = [
    "enable", "disable", "enabled", "live", "reset",
    "sampled", "span", "record_span", "annotate", "skew_tick",
    "setup", "note_setup", "note_executable", "avals_of", "scope_map",
    "flush", "trace_path", "spans", "skews", "snapshot",
    "skew_p99_ms", "skew_verdict", "critical_path",
]

_lock = _locklint.make_lock("trace.recorder")
_enabled = False          # the fast-path bool; hook sites read it directly
_dir = ""                 # per-rank files under <_dir>/<rank>/trace.jsonl
_rank_override = None
_sample_every = 1
_skew_every = 16
_buf = None               # pending records; None while disabled (zero-alloc)
_meta_paths = set()       # targets that already carry their meta line
_ticks = {}               # per-name counters: sampling for step-less spans
_agg = {}                 # (cat, name) -> [count, total_us] (critical path)
_skews = []               # skew probe records (bounded, drop-oldest)
_recorded = 0
_dropped = 0
_skew_failed = False      # a failed collective probe disables further ones
_flush_warned = False
_next_flush_try = 0.0     # monotonic backoff after a failed flush
_FLUSH_EVERY = 256        # buffered records per file append
_FLUSH_RETRY_S = 5.0      # wait after a failed flush before retrying
_MAX_BUF = 100_000        # in-memory record bound (with or without a dir)
_MAX_SKEWS = 4096

# gang-wide skew surfaced as ordinary telemetry series (no-ops while
# telemetry is disabled, like every other gauge in the registry)
_M_SKEW = _telemetry.gauge(
    "step_skew_seconds", "step-arrival spread across ranks at the "
    "collective boundary, from the last mx.trace skew probe (collectives "
    "serialize on the slowest arriver — this is the measured cost)")
_M_STRAGGLER = _telemetry.gauge(
    "straggler_rank", "rank that arrived LAST at the collective boundary "
    "in the last mx.trace skew probe — the gang's current straggler")


def enabled():
    """True when the span recorder is on (hot paths read the module
    global `_enabled` directly — this accessor is the public spelling)."""
    return _enabled


_session_live = _TraceMe.is_enabled   # jaxlib TraceMe: a session records


def live():
    """True when spans have somewhere to go: an operator called
    `enable()`, or a `jax.profiler` session is recording (start the
    profiler and the program's spans are in the capture). `Server.step`
    and `ShardedTrainer.step_async` read this once a step into a local;
    every span site tests that local."""
    return _enabled or _session_live()


def enable(trace_dir=None, rank=None, sample_every=None, skew_every=None):
    """Arm the recorder. Arguments override the `trace_dir` /
    `trace_sample_every` / `trace_skew_every` knobs (read once here — the
    per-span hot path never touches the config registry)."""
    global _enabled, _dir, _rank_override, _sample_every, _skew_every, _buf
    with _lock:
        if trace_dir is not None:
            _dir = str(trace_dir)
        elif not _dir:
            _dir = _config.get("trace_dir")
        if rank is not None:
            _rank_override = int(rank)
        _sample_every = max(1, int(
            sample_every if sample_every is not None
            else _config.get("trace_sample_every")))
        _skew_every = int(skew_every if skew_every is not None
                          else _config.get("trace_skew_every"))
        if _buf is None:
            _buf = []
        _enabled = True


def disable():
    global _enabled
    _enabled = False


def reset():
    """Drop recorded state (tests and run boundaries). While disabled the
    buffer itself is released, restoring the zero-allocation fast path."""
    global _buf, _recorded, _dropped, _sample_every, _skew_every
    global _skew_failed, _dir, _rank_override, _next_flush_try
    with _lock:
        _next_flush_try = 0.0
        _buf = [] if _enabled else None
        _ticks.clear()
        _agg.clear()
        del _skews[:]
        _meta_paths.clear()
        _recorded = 0
        _dropped = 0
        _skew_failed = False
        if not _enabled:
            # what enable() set goes back to its default, so a later
            # profiler session alone records every step
            _dir = ""
            _rank_override = None
            _sample_every, _skew_every = 1, 16


def _rank():
    if _rank_override is not None:
        return _rank_override
    for var in ("JAX_PROCESS_ID", "DMLC_WORKER_ID"):
        v = os.environ.get(var)
        if v is not None:
            try:
                return int(v)
            except ValueError:
                pass
    return 0


def _generation():
    """Which relaunch generation this process belongs to (the
    supervised-relaunch counter tools/launch.py exports; 0 standalone).
    Stamped into skew records so the offline cross-rank match pairs
    arrival stamps WITHIN a generation — a resumed gang replays step
    ids, and matching a survivor's replayed stamp against a dead rank's
    pre-restart stamp would read the restart backoff as arrival skew."""
    try:
        return int(os.environ.get("MXNET_TPU_RESTART_COUNT", "0"))
    except ValueError:
        return 0


def _gang_epoch_ns():
    """The shared gang trace epoch tools/launch.py --trace-dir exports
    (one wall timestamp for the whole gang), or None standalone."""
    v = os.environ.get("MXNET_TPU_TRACE_EPOCH_NS")
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        return None


def trace_path():
    """Where this rank's span file lands (None when trace_dir is unset)."""
    if not _dir:
        return None
    return os.path.join(_dir, str(_rank()), "trace.jsonl")


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------

def _trim_locked():
    """Drop-oldest bound on the record buffer (caller holds _lock),
    applied with OR without a trace_dir — an unwritable dir (every flush
    failing and re-queuing) must degrade to the same bounded in-memory
    buffer, not grow RSS. Trims in batches so eviction is amortized O(1)
    per span instead of an O(len) list shift per record once full."""
    global _dropped
    if len(_buf) > _MAX_BUF:
        cut = len(_buf) - _MAX_BUF + max(1, _MAX_BUF // 10)
        cut = min(cut, len(_buf))
        del _buf[:cut]
        _dropped += cut


def _flush_due_locked():
    """Whether the recorder should attempt a periodic flush (caller
    holds _lock). A failed flush backs off _FLUSH_RETRY_S so a full or
    read-only disk costs one open() per retry window, not one O(buffer)
    copy-and-fail per span."""
    return (bool(_dir) and len(_buf) >= _FLUSH_EVERY
            and time.monotonic() >= _next_flush_try)


def sampled(step):
    """True when `step` is one of the sampled steps (the trainer uses
    this to decide up front whether to stamp/fence/annotate a step)."""
    return step % _sample_every == 0


def record_span(name, t0, t1=None, step=None, cat="host", always=False,
                **extra):
    """Record one host-side span: `t0`/`t1` are raw time.perf_counter()
    readings (seconds; `t1` defaults to now), mapped onto the shared
    monotonic epoch. Sampling: `always` records unconditionally
    (compiles, checkpoints); a `step` records iff the step is sampled;
    step-less spans (input streams) sample on a per-name counter with
    the same stride. Returns True iff the span was recorded. Callers
    gate on the module bool — this function is never reached while
    disabled (ci sanity counts the calls)."""
    global _recorded, _dropped, _buf
    if not (_enabled or _session_live()):
        return False
    if t1 is None:
        t1 = time.perf_counter()
    with _lock:
        if _buf is None:
            # armed by a profiler session alone: the buffer is made on
            # first use and keeps the usual bound. disable()+reset()
            # racing a recording thread leaves it released
            if not (_enabled or _session_live()):
                return False
            _buf = []
        if not always:
            if step is not None:
                if step % _sample_every:
                    return False
            else:
                n = _ticks.get(name, 0)
                _ticks[name] = n + 1
                if n % _sample_every:
                    return False
        ev = {"kind": "span", "name": name, "cat": cat,
              "ts_us": round(_util.perf_to_us(t0), 1),
              "dur_us": round((t1 - t0) * 1e6, 1), "rank": _rank()}
        if step is not None:
            ev["step"] = int(step)
        if extra:
            ev.update(extra)
        a = _agg.get((cat, name))
        if a is None:
            _agg[(cat, name)] = [1, ev["dur_us"]]
        else:
            a[0] += 1
            a[1] += ev["dur_us"]
        _buf.append(ev)
        _recorded += 1
        _trim_locked()
        due = _flush_due_locked()
    if due:
        _safe_flush()
    return True


class span:
    """Context manager for one LIVE span (callers test their step's
    `live()` local first: `with (span(...) if tr else _NULLCTX):`). It
    enters a `jax.profiler.TraceAnnotation` of the same name, so the span
    is in the profiler's capture on the device trace's clock, and on exit
    appends to the buffer through `record_span`, so a reader in this
    process finds it in `spans()`. It never waits for the device. What is
    known only at the end (`admitted`, `tokens`) goes into `.attrs` before
    the block closes and reaches the buffer alone."""
    __slots__ = ("name", "cat", "step", "attrs", "keep", "_ann", "_t0")

    def __init__(self, name, cat="host", step=None, **attrs):
        self.name, self.cat, self.step, self.attrs = name, cat, step, attrs
        self.keep = True    # False: leave the buffer out (a wait for
        #                     end-of-stream is not an input stall)

    def __enter__(self):
        self._ann = _TraceMe(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self.keep:
            record_span(self.name, self._t0, t1, step=self.step,
                        cat=self.cat, **self.attrs)
        return False


def annotate(step):
    """Context manager wrapping one sampled step in a
    jax.profiler.TraceAnnotation carrying the same (rank, step) tag as
    the host spans, so XLA device traces and mx.trace timelines join on
    the step id. Only called for sampled steps while enabled."""
    import jax
    return jax.profiler.TraceAnnotation("mx.trace.step", step=int(step),
                                        rank=_rank())


# ---------------------------------------------------------------------------
# skew probe
# ---------------------------------------------------------------------------

def skew_tick(step):
    """Run the skew probe on every `trace_skew_every`-th SAMPLED step.
    The cadence is a pure function of the step id — NOT a local counter —
    because the multi-process probe is a blocking collective: every rank
    must reach it at the same global step, and a rank-local event (a
    jit-cache miss also calls this, and misses can be rank-local under
    bucketed shapes) must not desynchronize who probes when."""
    if not _enabled or _skew_every <= 0:
        return
    if step % _sample_every:
        return   # an always-traced (cache-miss) step that is not sampled
    if (step // _sample_every) % _skew_every:
        return
    _skew_probe(step)


def _skew_probe(step):
    """One probe: wall-stamp this rank's arrival; in a multi-process jax
    world all-gather the stamps so every rank sees the gang's spread and
    straggler live. Single-process worlds still record the local stamp —
    tools/trace_report.py cross-matches the per-rank records by step to
    measure the spread offline (the launch.py-without-jax.distributed
    case)."""
    global _skew_failed
    t_ns = time.time_ns()
    ts_us = _util.now_us()
    times = None
    try:
        jax = sys.modules.get("jax")
        # once a collective probe failed, never retry it this process:
        # a rank whose peers stopped answering must not block a sampled
        # step in an all-gather they will never join (stamps still
        # record — the offline step match needs no collective)
        if not _skew_failed and jax is not None \
                and jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils
            g = multihost_utils.process_allgather(
                np.asarray([t_ns], np.int64))
            times = [int(x) for x in np.asarray(g).ravel()]
    except Exception as e:  # pragma: no cover - backend-dependent
        if not _skew_failed:
            _skew_failed = True
            import warnings
            warnings.warn(f"mx.trace skew probe unavailable: {e}; "
                          "per-rank arrival stamps still record")
    if times is None:
        times = [t_ns]
    t_min = min(times)
    spread_s = (max(times) - t_min) / 1e9
    straggler = max(range(len(times)), key=lambda i: times[i]) \
        if len(times) > 1 else _rank()
    rec = {"kind": "skew", "ts_us": round(ts_us, 1), "step": int(step),
           "rank": _rank(), "t_wall_ns": t_ns, "gen": _generation(),
           "participants": len(times), "spread_s": round(spread_s, 6),
           "straggler_rank": straggler,
           "offsets_ns": [t - t_min for t in times]}
    global _dropped
    with _lock:
        _skews.append(dict(rec))
        if len(_skews) > _MAX_SKEWS:
            del _skews[0]
        due = False
        if _buf is not None:
            _buf.append(rec)
            _trim_locked()
            due = _flush_due_locked()
    _M_SKEW.set(spread_s)
    _M_STRAGGLER.set(straggler)
    _telemetry.event("trace_skew", step=int(step), spread_s=spread_s,
                     straggler_rank=straggler, participants=len(times))
    try:
        from . import diagnostics as _diagnostics
        _diagnostics.record_event("trace", step=int(step),
                                  spread_s=spread_s,
                                  straggler_rank=straggler)
    except Exception:
        pass
    if due:
        _safe_flush()


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def _meta_record():
    return {"kind": "meta", "schema": 1, "rank": _rank(),
            "pid": os.getpid(), "ts": time.time(),
            "epoch_unix_ns": _util.epoch_unix_ns(),
            "gang_epoch_ns": _gang_epoch_ns(),
            "sample_every": _sample_every, "skew_every": _skew_every}


def flush(path=None):
    """Append buffered records to `path` (default: this rank's
    trace_dir/<rank>/trace.jsonl) behind a one-time-PER-TARGET meta line
    (an explicit flush to a side path must not rob the rank file of the
    epoch anchor trace_report aligns on), and clear the buffer. Returns
    the path, or None when there is no target (the buffer then stays,
    bounded)."""
    path = path or trace_path()
    if path is None:
        return None
    global _next_flush_try
    with _lock:
        recs = list(_buf) if _buf else []
        if _buf:
            del _buf[:]
        need_meta = path not in _meta_paths
        _meta_paths.add(path)
    meta_ok = not need_meta
    written = 0
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # line-buffered: each write hands its line to the OS, so
        # `written` below reflects lines actually out the door — a
        # full-buffer deferral would otherwise surface the OSError at
        # close() with every record already counted (and then lost)
        with open(path, "a", buffering=1) as f:
            if need_meta:
                f.write(json.dumps(_meta_record()) + "\n")
                meta_ok = True
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
                written += 1
    except OSError:
        # a failed write must not lose the spans _safe_flush promises
        # stay buffered — but lines already handed to the OS before the
        # failure may be in the file, so only the UNWRITTEN suffix goes
        # back (front, order kept; a torn final line is skipped by
        # trace_report's loader, not duplicated), the meta line is only
        # re-armed when it never made it out, and retries back off
        with _lock:
            if not meta_ok:
                _meta_paths.discard(path)
            if _buf is not None:
                _buf[:0] = recs[written:]
                _trim_locked()
            _next_flush_try = time.monotonic() + _FLUSH_RETRY_S
        raise
    with _lock:
        _next_flush_try = 0.0
    return path


def _safe_flush():
    """Periodic flush that must not kill the training step it observes:
    an unwritable trace_dir warns once and keeps recording in memory."""
    global _flush_warned
    try:
        flush()
    except OSError as e:
        if not _flush_warned:
            _flush_warned = True
            import warnings
            warnings.warn(f"mx.trace flush to {trace_path()!r} failed: {e}; "
                          "spans stay buffered (warning once)")


def spans(tail=None):
    """Buffered (not yet flushed) span records, oldest first. `tail`
    bounds the work to the newest N spans — the scrape path (mx.scope
    /tracez) must not copy a 100k-record buffer under the same lock the
    step hot path's record_span takes, just to return 64 of them."""
    with _lock:
        if not _buf:
            return []
        if tail is None:
            return [dict(r) for r in _buf if r.get("kind") == "span"]
        out = []
        if tail > 0:
            for r in reversed(_buf):
                if r.get("kind") == "span":
                    out.append(dict(r))
                    if len(out) >= tail:
                        break
            out.reverse()
        return out


def skews():
    """Skew probe records this process, oldest first (kept in memory even
    after flushes, bounded)."""
    with _lock:
        return [dict(r) for r in _skews]


def snapshot():
    """Plain-data summary for the post-mortem "trace" section: sampling
    config, span/skew volume, this rank's file, and the last measured
    skew."""
    with _lock:
        return {
            "rank": _rank(),
            "sample_every": _sample_every,
            "skew_every": _skew_every,
            "spans_recorded": _recorded,
            "spans_buffered": len(_buf or ()),
            "spans_dropped": _dropped,
            "skew_probes": len(_skews),
            "last_skew": dict(_skews[-1]) if _skews else None,
            "path": trace_path(),
        }


def skew_p99_ms():
    """p99 of the measured multi-participant arrival spreads, in ms —
    None when no probe saw more than one participant (a single process
    cannot measure gang skew by itself; the merged report can)."""
    with _lock:
        spreads = sorted(s["spread_s"] for s in _skews
                         if s.get("participants", 1) > 1)
    if not spreads:
        return None
    idx = min(len(spreads) - 1, int(round(0.99 * (len(spreads) - 1))))
    return round(spreads[idx] * 1e3, 3)


def skew_verdict():
    """Live gang-skew summary for mx.scope's /statusz (the offline
    report in tools/trace_report.py stays the authoritative verdict —
    this is what a live scrape can know from THIS rank's probes): the
    last measured arrival spread, the suspected straggler rank, and the
    p99 across probes. None before any probe ran."""
    with _lock:
        last = dict(_skews[-1]) if _skews else None
        probes = len(_skews)
    if last is None:
        return None
    return {
        "probes": probes,
        "step": last.get("step"),
        "participants": last.get("participants", 1),
        "spread_ms": round(last.get("spread_s", 0.0) * 1e3, 3),
        "straggler_rank": last.get("straggler_rank"),
        "skew_p99_ms": skew_p99_ms(),
    }


def critical_path():
    """This rank's dominant STEADY-STATE span — the local leg of the
    gang critical path: {"span", "cat", "fraction", "total_s"} of the
    step/input span with the most recorded time, or None before any.
    Always-recorded compile/checkpoint spans are excluded: they are
    one-off seconds-scale events that would otherwise win every run
    (bench publishes this field — warmup compile time is not the
    critical path), the same exclusion tools/trace_report.py makes for
    its compute-bound dominant span."""
    with _lock:
        steady = {k: v for k, v in _agg.items()
                  if k[0] in ("step", "input", "serve")}
        if not steady:
            return None
        total = sum(t for _, t in steady.values())
        (cat, name), (count, t) = max(steady.items(),
                                      key=lambda kv: kv[1][1])
    if total <= 0:
        return None
    return {"span": name, "cat": cat, "fraction": round(t / total, 4),
            "total_s": round(t / 1e6, 6), "count": count}

# ---------------------------------------------------------------------------
# set-up table and device scopes
# ---------------------------------------------------------------------------

_setup = {"import_s": 0.0, "initialize_s": 0.0, "compile_s": 0.0,
          "compile_cache_hits": 0, "compile_cache_misses": 0}
_cache_events = {"/jax/compilation_cache/cache_hits": "compile_cache_hits",
                 "/jax/compilation_cache/cache_misses":
                 "compile_cache_misses"}
_executables = {}         # label -> Compiled, or (jitted, avals); newest
# `%copy.602 = ... metadata={op_name="jit(step)/.../kv_arena_update/..."`
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def note_setup(key, amount):
    """Add to one row of the set-up table. Set-up happens before any
    profiler session, so these are always-on sums of a handful of
    `perf_counter` differences taken where the work is seconds-scale
    (package import, `Block.initialize()`, a jit miss): never on a
    steady step."""
    _setup[key] += amount


def _on_cache_event(event, **_kw):
    key = _cache_events.get(event)
    if key is not None:
        _setup[key] += 1


def setup():
    """{"import_s", "initialize_s", "compile_s", "compile_cache_hits",
    "compile_cache_misses"}: where the process's set-up went, so far.
    `import_s` is the package's own `__init__`, start to end (jax and
    the array layer; submodules load lazily and are not in it)."""
    out = dict(_setup)
    out["import_s"] = getattr(sys.modules[__package__], "_import_s", 0.0)
    return out


def avals_of(args):
    """The (shape, dtype, sharding) pytree of a jitted call's arguments:
    it holds no buffer, and it keeps which arguments were committed to
    their device, so `jit.lower(*avals).compile()` answers from jax's
    in-memory cache with the executable the call itself built (no second
    compile)."""
    import jax

    def aval(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)

    return jax.tree.map(aval, args)


def note_executable(label, compiled, args=None):
    """Remember a step executable for `scope_map()` under `label` (a
    label keeps its newest). `compiled` is a `jax.stages.Compiled`; or,
    with `args`, the jitted function about to be called with them for the
    first time: the compile sites pass that, BEFORE the call (it donates
    its state), and only the arguments' avals are kept. Called at compile
    time only; nothing is lowered, compiled or read here."""
    _executables[label] = compiled if args is None \
        else (compiled, avals_of(args))


def scope_map(label=None):
    """{label: {HLO instruction name: op_name}} from the optimised HLO
    text of the executables handed to `note_executable` (all labels, or
    `label` alone). `op_name` is jax's path of named scopes
    (`jit(step)/.../kv_arena_update/scatter`): a device trace names events
    by instruction (`copy.602`), and this is the program's own map from
    there to the `jax.named_scope` the instruction was traced under.
    Built on demand, after the measurement: reading the text of a large
    executable takes seconds."""
    out = {}
    for lab, compiled in list(_executables.items()):
        if label is not None and lab != label:
            continue
        if isinstance(compiled, tuple):
            jitted, avals = compiled
            compiled = jitted.lower(*avals).compile()
        names = {}
        for line in compiled.as_text().splitlines():
            m = _HLO_LINE.match(line)
            if m:
                names[m.group(1)] = m.group(2)
        out[lab] = names
    return out


@atexit.register
def _flush_at_exit():
    if _enabled and _dir:
        try:
            flush()
        except OSError:
            pass  # nothing useful to do with a write error at interpreter exit


_monitoring.register_event_listener(_on_cache_event)

if _config.get("trace") == "on":
    enable()
