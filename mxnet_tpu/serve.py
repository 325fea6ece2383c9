"""mx.serve — overload-safe inference serving.

The training runtime is production-grade (elastic, never-OOM, guarded)
but a model that cannot answer a request serves nobody. This module is
the request path: a continuous-batching decode scheduler over ONE cache
manager, the mx.pages page pool, that never device-OOMs, never wedges
on a slow client, and sheds load gracefully instead of falling over.

Mechanics — the Orca-style token-level continuous batching loop over a
vLLM-style block table:

  * **one page pool, allocated at construction** — the KV store is
    `pages.PagePool`: refcounted fixed-size pages in one pooled arena
    per cache stream of the model (`model.serving_spec().streams`),
    `slots * max_len / page_size` pages unless `pool_pages` says
    otherwise. A request owns the LIST of pages its prompt + token
    budget needs, not a bucket-wide span.
  * **a prefix tree** — `pages.PrefixTree`, content-hashed over full
    prompt blocks: a finished prefill registers its pages, a later
    request with the same prefix starts past it with those pages mapped
    into its table (copy-on-write when its first write would land in a
    shared page).
  * **fixed batch slots, bucketed tables** — requests are grouped by
    the `dataflow.bucket_length` bucket of their total length (prompt +
    max_new_tokens, rounded up to whole pages); a bucket fixes the
    width of the page table its executables take, so a stream of novel
    lengths compiles a handful of executables per bucket — never one
    per length.
  * **the step is one pass over its tokens** — every scheduler step
    evicts expired slots, admits queued requests into free slots, and
    feeds, per active bucket, the last token of every decoding request
    and up to `pages_prefill_chunk` prompt tokens of every request
    still inside its prompt. The tokens are packed as VIRTUAL ROWS
    (token, position, the slot whose page-table row it goes through)
    and go through the model's `chunk_step` in the fewest passes a
    LADDER of widths allows (`Server._ladder`): `slots` rows, `2 *
    slots`, and doublings while a rung stays within 256 rows, the width
    past which a pass is no longer bound by its weights, and under
    `slots * pages_prefill_chunk`, the most a step can owe (a pass's
    weights and its dispatch are paid once a PASS). A pass runs the
    narrowest rung that holds its rows — one executable a rung and
    bucket (three or four for 32 slots), each built once when the
    bucket's first request is admitted (`Server._build`). Only a step
    that owes more than the top rung holds takes further passes, in
    admission order. A row's logits
    never depend on its neighbours, so a request's tokens are the same
    under load as alone (the tests hold them to `model.generate`'s on
    the CPU). Freshly sampled tokens stream to each request's consumer.
  * **a token is chosen where its logits are** — the step executable
    returns, beside the float32 logits, their argmax; the logits stay
    on the device and the host fetches the int32 ids, 4 bytes a row.
    A row of logits reaches the host only for a request that samples
    (`temperature > 0`: top-k softmax from the request's own seeded
    rng, on the host) or keeps them (`keep_logits`): those rows and no
    others, in one gather and one copy a pass (`Server._fetch_out`).
  * **a drafter** (`Server(drafter=...)`) adds draft-verify speculative
    decoding with exact greedy acceptance: the drafter chains `spec_k`
    proposals into the pool's `draft` stream, the target verifies them
    in one pass of `slots * (spec_k + 1)` rows, the host keeps the
    longest prefix that agrees with the ids of that pass. It changes
    WHEN tokens are computed, never which.

Robustness — the request lifecycle:

  * **admission control** — every accept is gated twice: mx.memsafe
    `check_budget` over resident parameters + the pool + the execution
    peak of the bucket's heaviest step executable (read from the
    builds the passes will run: the probe adds none), then the pool's
    free pages
    (after evicting unreferenced prefix-tree leaves). A predicted
    overrun is a `429`-style verdict on the request — never a device
    OOM, never a dispatched predicted-overrun batch.
  * **bounded queue, backpressure, load shedding** — the submit queue
    holds at most `serve_queue_depth` requests; beyond that the
    `serve_shed` policy rejects the newcomer (`reject`) or displaces
    the oldest waiter (`oldest`), each with a `503`-style verdict.
  * **deadlines with mid-generation cancellation** — a request carries
    an absolute deadline (`deadline_ms` or the `serve_deadline_ms`
    default); expired slots are evicted BETWEEN decode steps (partial
    tokens already streamed stay delivered) and their pages returned
    to the pool. `Server.cancel` / the `cancel@req:N` fault do the
    same on demand.
  * **retry/backoff on transient dispatch faults** — each batched step
    dispatch runs under `resilience.RetryPolicy` (exponential backoff,
    retryable-exception classification); donated-buffer safety is
    checked before every retry.
  * **graceful degradation under pressure** — one ladder, whichever
    refusal (bytes or pages) started it: (1) shrink the request's
    max_new_tokens to the largest smaller bucket it can be seated in
    (floored at `serve_min_new_tokens`), (2) when pages are what is
    short, evict-and-requeue the YOUNGEST running request (its replay
    is deterministic, already-streamed tokens are not re-sent), each
    transition annotated in telemetry, then (3) reject with the
    accounting only when nothing is running whose pages a wait could
    free.

Every path is deterministically testable: `resilience.FaultInjector`
grows `slow_client:ms` (stream consumer stalls; the scheduler must not
care), `burst:N@step:K` (K-th scheduler step injects N requests via
`Server.on_burst`) and `cancel@req:N` (mid-generation cancellation).
mx.guard heartbeats carry a `serve` phase; mx.trace spans cover
admit / queue-wait / decode-step / stream so `tools/trace_report.py`
can issue queue-bound vs decode-bound verdicts.

Cost model: DISABLED (the default) is the production fast path — the
decode dispatch hook site checks one module bool (`ci/run.sh sanity`
asserts zero `note_dispatch` calls). Constructing a `Server` arms it.
"""
from __future__ import annotations

import collections
import contextlib
import os
import queue as _pyqueue
import signal as _sig
import sys
import threading
import time
import weakref

import numpy as np

from . import _locklint
from . import config as _config
from . import diagnostics as _diagnostics
from . import goodput as _goodput
from . import guard as _guard
from . import memsafe as _memsafe
from . import pages as _pages
from . import resilience as _resilience
from . import slo as _slo
from . import telemetry as _telemetry
from . import trace as _trace
from . import util as _util

__all__ = [
    "Server", "Request", "enable", "disable", "enabled", "note_dispatch",
    "servers",
    "QUEUED", "RUNNING", "DONE", "REJECTED", "SHED", "EXPIRED",
    "CANCELLED", "FAILED", "TERMINAL",
]

# The width, in rows, at which a pass stops being bound by its weights: a
# bf16 weight is 2 bytes read from HBM and 2 operations a row, so reading
# and using it take the same time at peak-operations / peak-bytes rows, 197e12
# / 819e9 = 240 on a TPU v5e (`inspect._PEAK_FLOPS_TABLE`, `_PEAK_BW_TABLE`),
# as a power of two. A pass narrower than this costs what its weights cost
# to read, however few rows it has; the ladder of widths (`Server._ladder`)
# does not go past it.
_RIDGE_ROWS = 256

# request lifecycle states
QUEUED = "queued"        # accepted, waiting for a slot
RUNNING = "running"      # owns a batch slot, decoding
DONE = "done"            # all tokens generated (or eos)
REJECTED = "rejected"    # admission control refused (429-style)
SHED = "shed"            # load shedding dropped it (503-style)
EXPIRED = "expired"      # deadline passed; evicted between decode steps
CANCELLED = "cancelled"  # client/injected cancellation (499-style)
FAILED = "failed"        # scheduler error surfaced to the request (500)
TERMINAL = frozenset({DONE, REJECTED, SHED, EXPIRED, CANCELLED, FAILED})

_NULLCTX = contextlib.nullcontext()
# the step table's row (`mx.trace.STEP_FIELDS`), by field
(_R_STEP, _R_T0, _R_TOTAL, _R_SCHED, _R_PREP, _R_DISP, _R_FETCH, _R_STREAM,
 _R_PASSES, _R_FED, _R_WIDE, _R_ADMIT, _R_COMPILES, _R_COMPILE_S, _R_GAP,
 _R_POLLS, _R_WALKS) = range(len(_trace.STEP_FIELDS))
_ROW0 = (0.0,) * len(_trace.STEP_FIELDS)
_lock = _locklint.make_lock("serve.module")
_enabled = False          # the fast-path bool; the decode hook reads it
_dispatches = 0           # decode dispatches seen at the shared hook site
# live Server objects (weak: a dropped server must not be pinned by the
# registry) — mx.scope's /statusz surfaces each one's stats()
_servers = weakref.WeakSet()


def _close_round(step_span, stream_span, chunk, tokens):
    """Late attrs of one LIVE decode round: the tokens its `serve.stream`
    emitted, and on the step's `serve.step` span the largest `chunk` of
    its rounds' executables (`chunk` > 1: the step ran a pass wider than
    `slots` rows, or a speculative verify)."""
    stream_span.attrs["tokens"] = tokens
    if chunk > step_span.attrs.get("chunk", 0):
        step_span.attrs["chunk"] = chunk


def _fetch(out, dtype, row):
    """A step's result on the host (the token ids of its rows; the few
    logits rows asked for): the scheduler's wait for the device.

    It polls (`is_ready`, yielding the processor and the interpreter lock
    between polls) where `np.asarray` alone would sleep until the runtime
    wakes it. A thread woken by the runtime's thread can be left sharing
    that thread's core, and then EVERY step of the process runs about 3 ms
    longer (one scheduler timeslice; PERF.md section 6, PR 28: 8 of 27
    serving processes on the chip's host began so and stayed so for as
    long as they slept here; every one ran at the fast rate from its
    first polled wait on, and no polled stretch was ever slow). A
    scheduler thread that waits tens of milliseconds a step for its chip
    spends one host core on that; other threads run whenever it yields.

    The copy is asked for before the wait, so it follows the result with
    no round trip of its own (what a second round trip costs: PERF.md
    section 6, PR 35).

    One clock read a poll stamps the wait into the step table's `row`
    (`Server._row`):
    its count of polls, and its longest gap between two (tens of
    milliseconds: this thread was not scheduled; many short polls: the
    device was busy). A poll that finds the result ready reads no clock
    but the one before it."""
    out.copy_to_host_async()
    polls, gap, last = 1, 0.0, time.perf_counter()
    while not out.is_ready():
        time.sleep(0)
        now = time.perf_counter()
        if now - last > gap:
            gap = now - last
        last = now
        polls += 1
    row[_R_POLLS] += polls
    if gap > row[_R_GAP]:
        row[_R_GAP] = gap
    return np.asarray(out, dtype)


_take = None


def _take_rows(logits, want):
    """`logits[want]` on the device: the rows of a pass the host asked
    for, gathered so that one copy brings them. One jit for the process
    (its executables differ by the shapes alone: one for each number of
    rows a pass of that shape has been asked for)."""
    global _take
    if _take is None:
        import jax
        _take = jax.jit(lambda lg, i: lg[i])
    return _take(logits, np.asarray(want, np.int32))


def servers():
    """The live Server objects of this process (construction registers
    them; garbage collection removes them)."""
    return list(_servers)

_M_REQUESTS = _telemetry.counter(
    "serve_requests_total", "serving requests by terminal outcome "
    "(completed / rejected / shed / expired / cancelled / failed)")
_M_TOKENS = _telemetry.counter(
    "serve_tokens_total", "tokens generated and streamed by mx.serve")
_M_DEADLINE_MISS = _telemetry.counter(
    "serve_deadline_missed_total", "requests whose deadline expired "
    "(evicted between decode steps, or expired while still queued)")
_M_DEGRADED = _telemetry.counter(
    "serve_degraded_total", "graceful-degradation ladder transitions, by "
    "action: shrink_max_new (request admitted with a clamped token "
    "budget) or evict_requeue (youngest running request evicted and "
    "requeued to free KV pages)")
_M_TTFT = _telemetry.histogram(
    "serve_ttft_seconds", "time-to-first-token: submit to the first "
    "generated token landing in the request's stream")
_M_QWAIT = _telemetry.histogram(
    "serve_queue_wait_seconds", "time a request waited in the bounded "
    "queue before admission to a decode slot")
_M_QDEPTH = _telemetry.gauge(
    "serve_queue_depth", "requests currently waiting in the bounded "
    "admission queue (capacity serve_queue_depth)")
_M_ACTIVE = _telemetry.gauge(
    "serve_active_requests", "requests currently holding a decode slot")

_EOS_SENTINEL = object()


def enabled():
    """True while mx.serve instrumentation is armed (the decode dispatch
    hook reads the module bool directly; this is the public spelling)."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def note_dispatch(model_name, t0=None):
    """Decode-dispatch hook, called from `models/_decode.jit_flat_step`
    while serving is armed: counts every dispatch through the shared
    donated-KV decode path (the scheduler's own steps and any concurrent
    `generate()` traffic). Callers gate on the module bool — this
    function is never reached while disabled (ci sanity counts the
    calls)."""
    global _dispatches
    with _lock:
        _dispatches += 1


def dispatches():
    """Decode dispatches observed at the shared hook site this process."""
    with _lock:
        return _dispatches


def _fmt_bytes(n):
    from .util import fmt_bytes
    return fmt_bytes(n, show_raw=True)


# ---------------------------------------------------------------------------
# Request
# ---------------------------------------------------------------------------

class Request:
    """One generation request moving through the serving lifecycle.

    Public surface: `id` (admission-order sequence number — the N the
    `cancel@req:N` fault spec targets), `state` / `verdict` (terminal
    verdicts are HTTP-flavored: '200 ok', '429 ...', '503 ...',
    '504 deadline ...', '499 cancelled', '500 ...'), `tokens` (generated
    so far), `max_new_tokens` (EFFECTIVE — the shrink rung may clamp it,
    recorded in `degraded`), `logits` (under `submit(keep_logits=True)`:
    the float32 row behind each token, fetched for it), `requeues`, and
    the timing
    properties `queue_wait_s` / `ttft_s`.

    Consume results with `stream()` (yields tokens as they are
    generated; honors the `slow_client:ms` fault spec) or
    `result(timeout)` (blocks until terminal, returns the token array).
    Both need someone driving the scheduler: `Server.start()` (the
    background thread) or explicit `Server.step()`/`drain()` calls.
    """

    def __init__(self, seq, prompt, max_new_tokens, eos, temperature,
                 top_k, seed, deadline, keep_logits=False):
        self.id = seq
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.requested_new_tokens = int(max_new_tokens)
        self.eos = eos
        self.temperature = float(temperature or 0.0)
        self.top_k = int(top_k or 0)
        self.seed = int(seed)
        self.deadline = deadline          # absolute, on the server's clock
        self.state = QUEUED
        self.verdict = None
        self.tokens = []
        # the float32 logits row each token was chosen from, fetched and
        # kept on request (`submit(keep_logits=True)`)
        self.logits = [] if keep_logits else None
        self.degraded = None
        self.requeues = 0
        self.evicted_once = False         # each request triggers <= 1 evict
        self._streamed = 0                # replay high-water mark
        self._slo_j = None                # mx.slo journal (None while off)
        self._rng = None
        self._stream_q = _pyqueue.Queue()
        self._done = threading.Event()
        self._submit_perf = time.perf_counter()
        self._admit_perf = None
        self._first_token_perf = None
        self._finish_perf = None

    # -- consumer side ---------------------------------------------------
    def result(self, timeout=None):
        """Block until the request reaches a terminal state; returns the
        generated tokens as an int32 array (possibly partial — check
        `state`/`verdict`). Raises TimeoutError if the deadline passes
        with the request still live (the scheduler is not being driven,
        or the timeout was too tight)."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.id} still {self.state} after {timeout}s — "
                "is the server running? (Server.start() or drain())")
        return np.asarray(self.tokens, np.int32)

    def stream(self):
        """Iterate tokens as the scheduler generates them, ending when
        the request reaches a terminal state (partial on expiry/cancel).
        A `slow_client:ms` fault spec (mx.resilience) injects a per-token
        consumer stall here — the CLIENT side — which must never slow the
        scheduler itself down."""
        delay = None
        inj = _resilience._injector if _resilience._enabled else None
        if inj is not None:
            arg = inj.consume("slow_client")
            if arg:
                delay = float(arg) / 1000.0
                print(f"mx.serve: fault injection: slow client — "
                      f"{arg} ms stall per streamed token (request "
                      f"{self.id})", file=sys.stderr)
        if _slo._enabled and self._slo_j is not None:
            _slo.note_stream_start(self)
        try:
            while True:
                tok = self._stream_q.get()
                if tok is _EOS_SENTINEL:
                    return
                if delay:
                    time.sleep(delay)
                if self._slo_j is not None:
                    _slo.note_delivered(self)
                yield tok
        finally:
            # sentinel, break or a GC'd generator: either way the
            # delivery timeline is over — mx.slo can finalize
            if self._slo_j is not None:
                _slo.note_stream_end(self)

    @property
    def done(self):
        return self.state in TERMINAL

    @property
    def queue_wait_s(self):
        """Seconds spent queued before admission (None before admit)."""
        if self._admit_perf is None:
            return None
        return self._admit_perf - self._submit_perf

    @property
    def ttft_s(self):
        """Submit-to-first-token seconds (None before the first token)."""
        if self._first_token_perf is None:
            return None
        return self._first_token_perf - self._submit_perf

    def _reset_for_replay(self):
        """Requeue support: generation restarts from the prompt and —
        being deterministic per request (greedy, or the per-request rng
        reseeded here) — reproduces the same tokens; `_streamed` keeps
        already-delivered tokens from being re-sent."""
        self.tokens = []
        if self.logits is not None:
            self.logits = []
        self._rng = None
        self.requeues += 1
        self.state = QUEUED

    def __repr__(self):
        return (f"Request(id={self.id}, state={self.state!r}, "
                f"tokens={len(self.tokens)}/{self.max_new_tokens}"
                + (f", verdict={self.verdict!r}" if self.verdict else "")
                + ")")


# ---------------------------------------------------------------------------
# bucket group: the seats of one total-length bucket
# ---------------------------------------------------------------------------

class _Group:
    """The decode state for one length bucket: `slots` requests whose
    page tables are `n_pg = bucket // page_size` wide, so they share the
    bucket's step executables. It holds no cache — the pool is allocated
    once at server construction and priced there. Row i of `tables`, the
    (slots, n_pg) array the executables take, IS slot i's page table:
    its first `owned[i]` entries are the mx.pages page ids the slot
    holds one pool reference each for (`pages_of`), the rest 0, a scratch
    page. The array changes only as requests come and go (`seat` /
    `clear`, the only writers) and is not rebuilt every step; its copy
    on the device is made anew only after a change (`device_tables`).
    `pos[i]` is the next position slot i writes — while `pos <
    len(prompt)` the slot is prefilling, after that it consumes its own
    sampled tokens. `matched[i]` records how many prompt tokens arrived
    pre-filled from the prefix tree; `inserted[i]` latches the one-time
    tree insertion after the slot's prefill completes.

    A model with window page classes (`windows`, their windows in order)
    has one more table per class, `wtables[w]`, indexed like `tables` by
    `pos // page_size`. A slot holds the entries `wlo[w][i] .. whi[w][i]
    - 1` of its row there, one reference each in that class's allocator
    (`Server._window_grow` takes them as the position reaches them,
    `_window_trim` returns those the window has left); every other entry
    is 0, a scratch page, and is never walked. `wheld[w][i]` is what
    admission set aside for the slot in the class, a count. The
    executables then take all tables stacked, `tables` first."""

    __slots__ = ("bucket", "n_pg", "slots", "pos", "owned", "matched",
                 "inserted", "tables", "wtables", "wlo", "whi", "wheld",
                 "_tables_dev")

    def __init__(self, bucket, n_slots, n_pg, windows=()):
        self.bucket = bucket
        self.n_pg = n_pg
        self.slots = [None] * n_slots
        self.pos = [0] * n_slots
        self.owned = [0] * n_slots
        self.matched = [0] * n_slots
        self.inserted = [False] * n_slots
        self.tables = np.zeros((n_slots, n_pg), np.int32)
        self.wtables = {w: np.zeros((n_slots, n_pg), np.int32)
                        for w in windows}
        self.wlo = {w: [0] * n_slots for w in windows}
        self.whi = {w: [0] * n_slots for w in windows}
        self.wheld = {w: [0] * n_slots for w in windows}
        self._tables_dev = None

    def seat(self, i, req, pos0, pages, matched):
        self.slots[i] = req
        self.pos[i] = pos0
        self.matched[i] = matched
        self.inserted[i] = False
        self._set_pages(i, pages)

    def clear(self, i):
        self.slots[i] = None
        self.matched[i] = 0
        self.inserted[i] = False
        for w, table in self.wtables.items():
            table[i] = 0
            self.wlo[w][i] = self.whi[w][i] = self.wheld[w][i] = 0
        self._set_pages(i, ())

    def _set_pages(self, i, pages):
        self.owned[i] = len(pages)
        self.tables[i] = 0
        self.tables[i, :len(pages)] = pages
        self._tables_dev = None

    def pages_of(self, i):
        """The page ids slot i owns, in table order."""
        return self.tables[i, :self.owned[i]].tolist()

    def window_pages_of(self, i, w):
        """The page ids slot i holds in window class `w`, in table order."""
        return self.wtables[w][i, self.wlo[w][i]:self.whi[w][i]].tolist()

    def set_window_pages(self, i, w, lo, hi, pages=()):
        """Slot i's row of class `w` now holds entries lo..hi-1; `pages`
        are the ids of the last len(pages) of them, newly taken."""
        row = self.wtables[w][i]
        row[:lo] = 0
        row[hi - len(pages):hi] = pages
        self.wlo[w][i], self.whi[w][i] = lo, hi
        self._tables_dev = None

    def device_tables(self):
        """The page tables on the device: a copy (the host arrays go on
        changing), made again only after a slot was seated or cleared or
        a window class's row changed. One class: (slots, n_pg); more:
        (classes, slots, n_pg), the class that keeps everything first."""
        if self._tables_dev is None:
            import jax.numpy as jnp
            self._tables_dev = jnp.asarray(
                np.stack([self.tables, *self.wtables.values()])
                if self.wtables else self.tables.copy())
        return self._tables_dev

    def free_slot(self):
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def active(self):
        return [i for i, r in enumerate(self.slots) if r is not None]


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

class Server:
    """Continuous-batching inference server over one autoregressive
    model and one page pool. What it needs of the model it asks through
    `model.serving_spec()` (`models/_decode.ServingSpec`: vocabulary,
    longest position, the cache streams with their dtypes, the chunk
    step); the pool (`pages.PagePool`) and its prefix tree are built
    here, once, from `page_size`/`pool_pages` (the `pages_*` knobs).

    `submit()` never raises for overload — rejection, shedding and
    expiry are VERDICTS on the returned Request, so the scheduler loop
    cannot be crashed by traffic. Drive it with `start()`/`stop()` (a
    background thread), a `with` block, or synchronously via `step()` /
    `drain()` (tests inject `clock=` for deterministic deadlines).

    `slots`/`queue_depth`/`shed`/`default_deadline_ms`/`buckets` default
    to the `serve_*` knobs, `page_size`/`pool_pages`/`prefill_chunk`/
    `spec_k` to the `pages_*` ones. `pages` is what is left of a switch
    between this server and a dense per-bucket one that is gone: None
    and "on" are accepted (callers' configuration files pass "on").
    `on_burst(n)`, when set, is how the `burst:N@step:K` fault spec
    materializes synthetic load."""

    def __init__(self, model, slots=None, queue_depth=None, shed=None,
                 default_deadline_ms=None, buckets=None, max_len=None,
                 clock=None, retry=None, pages=None, drafter=None,
                 page_size=None, pool_pages=None, prefill_chunk=None,
                 spec_k=None):
        if pages not in (None, "on"):
            raise ValueError(
                f"pages={pages!r}: the dense per-bucket serving path is "
                "gone and every model is served through the page pool — "
                "drop the keyword (None and 'on' are accepted)")
        enable()
        self.model = model
        self._spec = spec = model.serving_spec()
        self._max_len = int(max_len or spec.max_length)
        self._drafter = drafter
        self._draft_spec = None
        if drafter is not None:
            self._draft_spec = drafter.serving_spec()
            if self._draft_spec.draft_step is None:
                raise ValueError(
                    f"{type(drafter).__name__} cannot draft: its serving "
                    "spec has no draft_step")
        self._slots = int(slots or _config.get("serve_slots"))
        self._queue_depth = int(queue_depth
                                if queue_depth is not None
                                else _config.get("serve_queue_depth"))
        shed = shed or _config.get("serve_shed")
        if shed not in ("reject", "oldest"):
            raise ValueError(
                f"serve_shed must be 'reject' or 'oldest', got {shed!r}")
        self._shed = shed
        self._default_deadline_ms = float(
            default_deadline_ms if default_deadline_ms is not None
            else _config.get("serve_deadline_ms"))
        self._buckets = self._parse_buckets(buckets)
        self._clock = clock or time.monotonic
        self._retry = retry or _resilience.RetryPolicy()
        self._lock = _locklint.make_rlock("serve.server")
        self._queue = collections.deque()
        self._groups = {}          # bucket -> _Group
        self._runners = {}         # (kind, bucket, ...) -> jit_flat_step runner
        self._warmed = set()       # buckets whose executables are compiled
        self._unfit = {}           # bucket -> the device's refusal at _warm
        self._width_dispatches = collections.Counter()   # width -> passes
        self._exec_peaks = {}      # bucket -> AOT exec-peak bytes (or None)
        self._built = {}           # bucket -> its passes' Compiled (`_build`)
        self._by_id = {}
        self._pending_cancels = []
        self._seq = 0
        self._sched_step = 0
        self._row = list(_ROW0)    # this step's row of `mx.trace.steps()`
        self._stats = {
            "submitted": 0, "completed": 0, "rejected": 0, "shed": 0,
            "expired": 0, "cancelled": 0, "failed": 0, "tokens": 0,
            "steps": 0, "requeues": 0, "degraded": 0, "retries": 0,
            "prompt_tokens": 0, "prefix_tokens": 0, "prefix_hits": 0,
            "chunk_dispatches": 0, "chunk_steps": 0, "token_steps": 0,
            "spec_rounds": 0,
            # the fill of the passes: virtual rows dispatched (the sum of
            # the widths) and the tokens fed in them
            "rows_dispatched": 0, "rows_fed": 0,
            # what the passes brought to the host: tokens emitted from the
            # ids the device chose, the float32 logits rows copied (for
            # requests that sample or keep them), and all bytes copied
            "rows_sampled_on_device": 0, "logit_rows_fetched": 0,
            "fetched_bytes": 0,
            "drafts_proposed": 0, "drafts_accepted": 0,
            # what attention was fed, worked out from positions alone:
            # tokens, the sum of their context lengths, of the contexts
            # cut to `index_topk`, and the tokens whose context passed it
            "attn_tokens": 0, "attn_ctx_tokens": 0, "attn_sel_tokens": 0,
            "sparse_tokens": 0,
        }
        # the model's window page classes, by window (`ServingSpec.windows`;
        # none: one class, and everything below is as it was without them)
        self._windows = tuple(sorted(
            {w for w in (spec.windows or ()) if w is not None}))
        if self._windows:
            if drafter is not None:
                raise ValueError(
                    "a drafter beside a model with window page classes is "
                    "not supported")
            # of the contexts cut to each window class's window, and the
            # pages those classes gave back (`stats()` shows them)
            self._stats.update(attn_window_tokens=0, window_pages_freed=0)
        # the pool and its prefix tree. The usable position range rounds
        # DOWN to a page multiple and buckets round UP to one
        # (`_bucket_for`), so a bucket's page table covers it exactly.
        # The default pool holds `slots * max_len/page_size` data pages:
        # every slot at the longest servable length.
        ps = int(page_size or _config.get("pages_page_size"))
        if ps < 1:
            raise ValueError(f"pages_page_size must be >= 1, got {ps}")
        self._page_size = ps
        self._prefill_chunk = max(
            1, int(prefill_chunk or _config.get("pages_prefill_chunk")))
        self._spec_k = max(1, int(spec_k or _config.get("pages_spec_k")))
        self._rungs = self._ladder(self._slots, self._prefill_chunk)
        if self._max_len < ps:
            raise ValueError(
                f"pages_page_size {ps} exceeds the model's max_length "
                f"{self._max_len} — no position fits a single page")
        self._max_len = (self._max_len // ps) * ps
        self._params_bytes = self._measure_params(model)
        streams = {"target": list(spec.streams)}
        if drafter is not None:
            streams["draft"] = list(self._draft_spec.streams)
            self._params_bytes += self._measure_params(drafter)
        data = int(pool_pages or _config.get("pages_pool_pages")) \
            or self._slots * (self._max_len // ps)
        # a window class holds what every slot can have inside its window:
        # worked out, not a knob (`_window_need`)
        self._pool = _pages.PagePool(
            ps, data, self._slots, streams,
            windows={"target": spec.windows} if self._windows else None,
            window_pages={w: self._slots * self._window_need(w)
                          for w in self._windows})
        self._tree = _pages.PrefixTree(self._pool)
        from . import check as _check
        if _check._enabled:
            smallest = self._buckets[0] if self._buckets is not None \
                else max(1, int(_config.get("bucket_pad_min")))
            _check.lint_paging(
                f"serve.Server(page_size={ps})", ps, smallest,
                spec.vocab_size,
                None if drafter is None else self._draft_spec.vocab_size)
        self.on_burst = None
        self._thread = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._error = None
        self._stopped = False
        _servers.add(self)

    # -- construction helpers -------------------------------------------
    def _parse_buckets(self, buckets):
        if buckets is None:
            raw = _config.get("serve_buckets")
            buckets = [int(b) for b in str(raw).split(",") if b.strip()] \
                if raw else None
        if buckets is None:
            return None                       # pow2 policy
        bl = sorted(int(b) for b in buckets)
        if not bl:
            raise ValueError("serve buckets: empty list")
        if bl[-1] > self._max_len:
            raise ValueError(
                f"serve bucket {bl[-1]} exceeds the model's max_length "
                f"{self._max_len}")
        return bl

    @staticmethod
    def _measure_params(model):
        try:
            return _memsafe.resident_bytes(
                [p.data()._data for p in model.collect_params().values()])
        except Exception:
            return 0

    def _window_need(self, window, total=None):
        """The most pages of the class of `window` a request ever holds
        at once: those its window spans however it lies on the page grid
        (`window / page_size`, and one more), and those the rows of one
        pass (`prefill_chunk` at most) reach past it; never more than a
        request of `total` positions has."""
        ps = self._page_size
        need = -(-(window + self._prefill_chunk - 1) // ps) + 1
        return need if total is None else min(need, -(-total // ps))

    # -- client surface --------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, eos=None, temperature=0.0,
               top_k=0, seed=0, deadline_ms=None, keep_logits=False):
        """Enqueue one generation request; returns a Request immediately
        (possibly already terminal: shed when the bounded queue is full
        under `serve_shed=reject`, or rejected when the request cannot
        fit the device even alone). Never raises for overload.
        `keep_logits` keeps on `Request.logits` the float32 row each
        token was chosen from (an audit: that row is fetched beside the
        pass's token ids, 4 x vocabulary bytes a token)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or int(max_new_tokens) <= 0:
            raise ValueError("submit needs a non-empty prompt and "
                             "max_new_tokens >= 1")
        ms = deadline_ms if deadline_ms is not None \
            else (self._default_deadline_ms or None)
        deadline = (self._clock() + float(ms) / 1000.0) if ms else None
        with self._lock:
            req = Request(self._seq, prompt, max_new_tokens, eos,
                          temperature, top_k, seed, deadline, keep_logits)
            self._seq += 1
            self._by_id[req.id] = req
            self._stats["submitted"] += 1
            # journal BEFORE any admission verdict: rejected and shed
            # requests are exactly the ones mx.slo must explain
            if _slo._enabled:
                _slo.note_submit(req)
            # a dead scheduler must fail fast, not enqueue a request no
            # thread will ever drive (the client would wedge in result())
            if self._error is not None:
                self._finish(req, FAILED,
                             f"500 scheduler failed earlier: "
                             f"{type(self._error).__name__}: {self._error}")
                return req
            if self._stopped:
                self._finish(req, SHED, "503 server stopped")
                return req
            need = prompt.size + int(max_new_tokens)
            if need > self._max_len:
                self._finish(req, REJECTED,
                             f"413 too long: prompt {prompt.size} + "
                             f"max_new_tokens {max_new_tokens} exceeds "
                             f"max_length {self._max_len}")
                return req
            over = self._solo_overrun()
            if over is not None:
                self._finish(req, REJECTED, over)
                return req
            if len(self._queue) >= self._queue_depth:
                if self._shed == "reject":
                    self._finish(req, SHED,
                                 "503 shed: queue full "
                                 f"({self._queue_depth} deep, "
                                 "serve_shed=reject)")
                    return req
                oldest = self._queue.popleft()
                self._finish(oldest, SHED,
                             "503 shed: displaced by newer request "
                             f"{req.id} (serve_shed=oldest)")
            self._queue.append(req)
            if _telemetry._enabled:
                _M_QDEPTH.set(len(self._queue))
        self._wake.set()
        return req

    def cancel(self, req_or_id):
        """Cancel a request: removed from the queue immediately, or — if
        running — evicted between decode steps (partial tokens stay
        delivered). No-op on already-terminal requests."""
        req = self._by_id.get(req_or_id) \
            if not isinstance(req_or_id, Request) else req_or_id
        if req is None:
            return
        with self._lock:
            self._pending_cancels.append(req)
        self._wake.set()

    def stats(self):
        """Counter snapshot plus live occupancy (plain dict)."""
        with self._lock:
            out = dict(self._stats)
            out["queued"] = len(self._queue)
            out["running"] = sum(len(g.active())
                                 for g in self._groups.values())
            out["buckets_allocated"] = sorted(self._groups)
            out["executables"] = len(self._runners)
            out["width_dispatches"] = dict(self._width_dispatches)
            out["scheduler_steps"] = self._sched_step
            out["pages"] = "on"     # readers' key from when it was a mode
            out["page_size"] = self._page_size
            out["pool_pages_total"] = self._pool.data_pages
            out["pool_pages_free"] = self._pool.free_pages()
            out["tree_nodes"] = len(self._tree.nodes)
            out["tree_evicted_pages"] = self._tree.stats["evicted_pages"]
            out["cow_copies"] = self._pool.stats["cow_copies"]
            if self._windows:
                out["pages_in_use"] = {
                    "full": self._pool.used_pages(),
                    **{f"window{w}": c.used_pages()
                       for w, c in self._pool.windows.items()}}
            pt = self._stats["prompt_tokens"]
            out["prefix_hit_rate"] = (
                self._stats["prefix_tokens"] / pt if pt else 0.0)
            dp = self._stats["drafts_proposed"]
            out["accepted_draft_rate"] = (
                self._stats["drafts_accepted"] / dp if dp else 0.0)
        out["dispatches"] = dispatches()
        return out

    def admission_hints(self):
        """What a fleet router needs to PREDICT this server's admission
        verdict without a round trip: the pool's page size and free-page
        count, and memsafe's headroom beside parameters and pool. A None
        `headroom_bytes` means memsafe is off — nothing to predict.
        Published per replica via the mx.fleet /statusz payload; the
        router skips replicas whose hints predict a 429 (the
        memory-safe-by-prediction discipline, one level up)."""
        out = {"max_len": self._max_len, "slots": self._slots,
               "queue_depth": self._queue_depth,
               "buckets": self._buckets,       # None => pow2 policy
               "page_size": self._page_size}
        cap = _memsafe.capacity_bytes()
        if cap is None:
            out["headroom_bytes"] = None
            return out
        with self._lock:
            out["pool_pages_free"] = self._pool.free_pages()
            if self._windows:
                # what admission has not set aside yet, per window class
                out["window_pages_free"] = {
                    w: c.data_pages - self._window_held(w)
                    for w, c in self._pool.windows.items()}
        out["headroom_bytes"] = max(
            0, int(cap) - self._params_bytes - self._pool.pool_bytes())
        return out

    # -- lifecycle -------------------------------------------------------
    def start(self):
        """Run the scheduler in a background thread until `stop()`."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stopped = False
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="mx-serve-scheduler", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        """Stop the background scheduler; outstanding (non-terminal)
        requests are finished with a '499 server stopped' verdict so no
        client blocks forever."""
        self._stopped = True
        self._stop.set()
        self._wake.set()
        if self._thread is not None \
                and self._thread is not threading.current_thread():
            self._thread.join(timeout=10)
        with self._lock:
            live = [r for r in self._by_id.values()
                    if r.state not in TERMINAL]
            for r in live:
                self._remove_from_slots(r)
                self._finish(r, CANCELLED, "499 server stopped")
            self._queue.clear()
            self._gc_groups()
            # drop the tree's page references so the pool drains fully
            # (every page back on the free list)
            self._tree.clear()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _loop(self):
        while not self._stop.is_set():
            try:
                work = self.step()
            except Exception as e:  # noqa: BLE001 — surfaced to requests
                self._scheduler_failed(e)
                return
            if not work:
                if _goodput._enabled:
                    # an empty scheduler pass is queue-idle wall-clock
                    # (coalesced write-side — one record per idle span,
                    # not one per 5 ms poll)
                    t0 = time.perf_counter()
                    self._wake.wait(0.005)
                    _goodput.note("serve_idle", t0)
                else:
                    self._wake.wait(0.005)
                self._wake.clear()

    def _scheduler_failed(self, exc):
        """A non-overload error escaped a scheduler step (overload paths
        — budget, deadline, shed, cancel — are all verdicts and cannot
        reach here). Fail every live request with a 500 verdict so no
        client wedges on a dead scheduler, and keep the error for
        `raise_if_failed`."""
        self._error = exc
        print(f"mx.serve: scheduler error: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        if _diagnostics._enabled:
            _diagnostics.record_event("serve", action="scheduler_error",
                                      error=f"{type(exc).__name__}: {exc}")
        with self._lock:
            for r in list(self._by_id.values()):
                if r.state not in TERMINAL:
                    self._remove_from_slots(r)
                    self._finish(r, FAILED,
                                 f"500 scheduler error: "
                                 f"{type(exc).__name__}: {exc}")
            self._queue.clear()

    def raise_if_failed(self):
        if self._error is not None:
            raise self._error

    def busy(self):
        """True while any request is queued or holds a slot."""
        with self._lock:
            if self._queue or self._pending_cancels:
                return True
            return any(g.active() for g in self._groups.values())

    def drain(self, max_steps=100_000):
        """Drive the scheduler synchronously until idle (tests and batch
        use). Raises RuntimeError after `max_steps` — a wedged scheduler
        must fail loudly, not hang the caller."""
        n = 0
        while self.busy():
            self.step()
            n += 1
            if n >= max_steps:
                raise RuntimeError(
                    f"mx.serve: scheduler still busy after {max_steps} "
                    f"steps — {self.stats()}")
        return n

    # -- scheduler -------------------------------------------------------
    def step(self):
        """One scheduler iteration: fire injected faults, evict expired
        slots, admit from the queue (admission control + degradation
        ladder), run one batched decode step per active bucket, stream
        the new tokens. Returns True while work remains. Overload never
        raises out of here — only scheduler bugs do."""
        # mx.trace: read once a step; every span site below tests this
        # local (off: one call and a handful of bool tests). The step
        # table's row is written whether or not (`mx.trace.steps()`)
        tr = _trace.live()
        row = self._row
        row[:] = _ROW0
        compiles0, compile_s0 = _trace.compiled
        t0 = time.perf_counter()
        with (_trace.span("serve.step", cat="phase",
                          step=self._sched_step + 1)
              if tr else _NULLCTX) as sp:
            with (_trace.span("serve.schedule", cat="phase")
                  if tr else _NULLCTX) as sched:
                with self._lock:
                    self._sched_step += 1
                    n = self._sched_step
                self._fire_faults(n)
                if _guard._enabled:
                    _guard.heartbeat(phase="serve")
                # bucket executables and their AOT peaks are built OUTSIDE
                # the lock (an XLA compile is seconds on a real model;
                # submit/cancel from client threads must not block behind
                # it)
                self._prewarm_buckets()
                with self._lock:
                    self._apply_cancels()
                    self._evict_expired()
                    # forget drained buckets BEFORE admission (their
                    # pages went back to the pool as each slot emptied,
                    # so a cancel/expiry of this very step already
                    # counts for the incoming request)
                    self._gc_groups()
                    queued = len(self._queue)
                    self._admit()
                    row[_R_ADMIT] = queued - len(self._queue)
                    if tr:
                        sched.attrs["admitted"] = row[_R_ADMIT]
                        sched.step = n
                    groups = [g for g in self._groups.values()
                              if g.active()]
                # a bucket's executables are compiled once its first
                # request is seated, which is after `_seat` found its step
                # within the byte budget and before its first dispatch;
                # outside the lock, like every compile
                groups = [g for g in groups if self._warm_seated(g)]
            row[_R_SCHED] = time.perf_counter() - t0
            for grp in groups:
                if not _goodput._enabled:
                    self._decode_group(grp, n, sp)
                    continue
                # decode time for a batch holding any degraded/requeued
                # request is "serve_degraded" — capacity spent delivering
                # below-contract service rather than clean goodput
                with self._lock:
                    degr = any(grp.slots[i].degraded or grp.slots[i].requeues
                               for i in grp.active())
                g0 = time.perf_counter()
                self._decode_group(grp, n, sp)
                _goodput.note("serve_degraded" if degr else "serve_decode",
                              g0)
            with self._lock:
                self._gc_groups()
                if _telemetry._enabled:
                    _M_QDEPTH.set(len(self._queue))
                    _M_ACTIVE.set(sum(len(g.active())
                                      for g in self._groups.values()))
        busy = self.busy()
        row[_R_TOTAL] = time.perf_counter() - t0
        row[_R_STEP] = n
        row[_R_T0] = _util.perf_to_us(t0)
        row[_R_COMPILES] = _trace.compiled[0] - compiles0
        row[_R_COMPILE_S] = _trace.compiled[1] - compile_s0
        _trace.note_step(row)
        return busy

    def _prewarm_buckets(self):
        """The AOT exec-peak probe of every bucket the queue will ask
        for, before the locked admission pass: what `_admit_budget`
        reads. It builds the bucket's executables (`_build`), analyses
        them and dispatches nothing; `_warm_seated` runs them on padding
        once a request has been seated. Only the scheduler thread touches
        _runners / _built / _exec_peaks, so no lock is required here."""
        if _memsafe.capacity_bytes() is None:
            return
        with self._lock:
            pending = [r for r in self._queue if r.state == QUEUED]
        for r in pending:
            self._exec_peak(
                self._bucket_for(r.prompt.size + r.max_new_tokens))

    @staticmethod
    def _ladder(slots, prefill_chunk):
        """The widths, in virtual rows, a bucket's passes may take: `slots`
        (every decode-only step fits it), `2 * slots`, and doublings
        while a rung stays within `_RIDGE_ROWS` (past it a pass is no
        longer bound by its weights, and two passes cost what one of
        twice the width does) and under `slots * prefill_chunk`, the
        most a step can owe: the one step in which every slot feeds a
        whole chunk takes two passes of the rung below, and an
        executable nothing else would fill is not built (a build is
        1-2 s of set-up a bucket, PERF.md section 6, PR 38). One
        executable a rung and bucket. Derived, not a knob. With
        `prefill_chunk` 1 no step has more tokens than slots and there is
        one width."""
        if prefill_chunk == 1:
            return (slots,)
        rungs = [slots, 2 * slots]
        while 2 * rungs[-1] <= _RIDGE_ROWS \
                and 2 * rungs[-1] < slots * prefill_chunk:
            rungs.append(2 * rungs[-1])
        return tuple(rungs)

    def _wide(self):
        """The top rung of the ladder: the most rows one pass takes, where
        `_passes` cuts a step's feeds, and the executable `lower_step`
        lowers."""
        return self._rungs[-1]

    def _warm_seated(self, grp):
        """`_warm` the bucket of a group that holds requests; True when
        the group can be stepped. A device that refuses the bucket after
        all (RESOURCE_EXHAUSTED from the compiler or from the padding
        dispatch, the pool's buffers intact) says what the budget would
        have said had its prediction been right, and is answered the
        same way: the requests seated in the bucket get the 429 with the
        device's words, and the bucket is struck (`_unfit`), so `_seat`
        refuses it from now on and the ladder shrinks later requests
        below it. Anything else is a scheduler error and raises."""
        if grp.bucket in self._warmed:
            return True
        try:
            self._warm(grp.bucket)
            return True
        except Exception as e:      # noqa: BLE001 — sorted just below
            if not _memsafe.is_oom(e) or not self._pool_intact():
                raise
            refusal = e
        print(f"mx.serve: bucket {grp.bucket} does not fit the device "
              f"after all, refused from now on: {refusal}", file=sys.stderr)
        with self._lock:
            self._unfit[grp.bucket] = refusal
            for i in grp.active():
                r = grp.slots[i]
                self._vacate(grp, i)
                self._finish(r, REJECTED, f"429 over capacity: {refusal}")
        return False

    def _pool_intact(self):
        """False once a failed dispatch has consumed donated arenas."""
        return not any(hasattr(a, "is_deleted") and a.is_deleted()
                       for arenas in self._pool.state.values()
                       for a in arenas)

    def _bucket_passes(self, bucket):
        """Every pass `bucket` can run, as (width, full, tag): the
        ladder's rungs; under a drafter also its mirror of each (gap-0
        sync) and the verify pass (the draft chain is `_draft_runner`'s)."""
        passes = [(w, False, "target") for w in self._rungs]
        if self._drafter is not None:
            passes += [(w, False, "draft") for w in self._rungs]
            passes.append((self._slots * (self._spec_k + 1), True, "target"))
        return passes

    def _build(self, bucket):
        """Build the executable of every pass of `bucket`, each ONCE, and
        return them as {(width, full, tag): jax.stages.Compiled}. A pass
        is traced, lowered and compiled through the jit its calls use
        (`jit_flat_step`'s `lower`, at `_step_avals`, the calls' avals),
        so the call finds the executable there and builds none, and the
        memory analysis of these very executables is the admission's
        probe (`_exec_peak`), which adds no build. The seconds go to
        `mx.trace.setup()["compile_s"]`. (Compiling a rung on a worker
        thread while the next is traced was measured and lost: PERF.md
        section 6, PR 38.)"""
        built = self._built.get(bucket)
        if built is not None:
            return built
        t0 = time.perf_counter()
        built = {
            (width, full, tag):
            self._runner(bucket, width, full, draft=tag == "draft")
            .lower(*self._step_avals(bucket, width, tag)).compile()
            for width, full, tag in self._bucket_passes(bucket)}
        _trace.note_setup("compile_s", time.perf_counter() - t0)
        self._built[bucket] = built
        return built

    def _warm(self, bucket):
        """Make every executable `bucket` can run ready, once, when its
        first request has been seated: built (`_build`; by the
        admission's probe already, where the device's capacity is known)
        and each dispatched on a pass of padding rows (position -1: they
        walk no page and write slot 0's scratch page, which nothing
        reads). So no executable is first built inside a timed stretch of
        steps, whichever width a loop needs first, and
        `stats()["executables"]` does not grow after the step of the
        admission. The small gathers of `_take_rows` are compiled here
        for the requests held at that step, and for a later one when its
        first token asks (PERF.md section 6, PR 35, has what that
        costs)."""
        import jax.numpy as jnp
        S, n_pg = self._slots, bucket // self._page_size
        self._build(bucket)
        idle = _Group(bucket, S, n_pg, self._windows)   # all padding
        with self._lock:
            held = [*self._queue, *(r for g in self._groups.values()
                                    for r in g.slots)]
        asking = sum(self._wants_row(r) for r in held)
        for width, full, tag in self._bucket_passes(bucket):
            logits, _ = self._dispatch(
                idle, self._runner(bucket, width, full, draft=tag == "draft"),
                (*self._pack_rows(width), idle.device_tables()), tag)
            if tag == "target":
                # the gathers that bring the logits rows the requests held
                # now can ask of such a pass (`_fetch_out`); a count that
                # a later request adds is compiled when it first occurs
                most = asking * (self._spec_k + 1 if full else 1)
                for n in range(1, min(most, logits.shape[0]) + 1):
                    _take_rows(logits, [0] * n)
        if self._drafter is not None:
            blank = jnp.zeros((S,), jnp.int32)
            self._dispatch(idle, self._draft_runner(bucket),
                           (blank, blank, jnp.zeros((S,), bool),
                            idle.device_tables()), "draft")
        self._warmed.add(bucket)

    def _fire_faults(self, sched_step):
        inj = _resilience._injector if _resilience._enabled else None
        if inj is None:
            return
        hit = inj.take("burst", step=sched_step)
        if hit is not None:
            count = int(hit["arg"] or 1)
            print(f"mx.serve: fault injection: burst of {count} at "
                  f"scheduler step {sched_step}", file=sys.stderr)
            if self.on_burst is not None:
                self.on_burst(count)
        # a step-less cancel spec waits, still armed, until its target
        # request has actually been submitted — consuming it at scheduler
        # step 1 of an idling background server would silently no-op the
        # documented cancellation drill
        hit = inj.take("cancel", step=sched_step,
                       ready=lambda spec: spec["req"] is not None
                       and spec["req"] in self._by_id)
        if hit is not None:
            rid = hit.get("req")
            print(f"mx.serve: fault injection: cancel request {rid} at "
                  f"scheduler step {sched_step}", file=sys.stderr)
            if rid is not None:
                self.cancel(int(rid))
        # fleet drills, fired from the scheduler so they land mid-
        # generation: kill_replica is the SIGKILLed-worker failover
        # drill (the router must replay in-flight requests on a
        # survivor); wedge_replica parks the scheduler forever WITHOUT
        # holding the lock — health checks keep answering, tokens stop,
        # exactly the stalled-but-alive replica the router's per-read
        # stall bound exists for
        hit = inj.take("kill_replica", step=sched_step)
        if hit is not None:
            print(f"mx.serve: fault injection: kill_replica at scheduler "
                  f"step {sched_step} (pid {os.getpid()})", file=sys.stderr)
            sys.stderr.flush()
            os.kill(os.getpid(), _sig.SIGKILL)
        hit = inj.take("wedge_replica", step=sched_step)
        if hit is not None:
            print(f"mx.serve: fault injection: wedge_replica at scheduler "
                  f"step {sched_step} — scheduler parked, process alive",
                  file=sys.stderr)
            sys.stderr.flush()
            while True:
                time.sleep(3600)

    def _apply_cancels(self):
        pending, self._pending_cancels = self._pending_cancels, []
        for req in pending:
            if req.state in TERMINAL:
                continue
            self._remove_from_slots(req)
            try:
                self._queue.remove(req)
            except ValueError:
                pass
            self._finish(req, CANCELLED,
                         f"499 cancelled after {len(req.tokens)} tokens")

    def _evict_expired(self):
        now = self._clock()
        for grp in self._groups.values():
            for i in grp.active():
                r = grp.slots[i]
                if r.deadline is not None and now > r.deadline:
                    self._vacate(grp, i)
                    self._note_deadline_miss(r, running=True)
        for r in list(self._queue):
            if r.deadline is not None and now > r.deadline:
                self._queue.remove(r)
                self._note_deadline_miss(r, running=False)

    def _note_deadline_miss(self, req, running):
        if _telemetry._enabled:
            _M_DEADLINE_MISS.inc()
        where = (f"evicted mid-generation after {len(req.tokens)} tokens "
                 "(KV pages reclaimed)") if running else "expired in queue"
        self._finish(req, EXPIRED, f"504 deadline: {where}")

    # -- admission -------------------------------------------------------
    def _bucket_for(self, need):
        from . import dataflow as _dataflow
        if self._buckets is not None:
            b = _dataflow.bucket_length(need, self._buckets)
        else:
            b = _dataflow.bucket_length(need, "pow2")
        # buckets are page multiples, so a bucket's page table
        # (n_pg * page_size positions) covers it exactly (pow2 buckets
        # with a pow2 page size are already multiples; construction
        # rounded _max_len down, so the cap stays a multiple too)
        ps = self._page_size
        return min(-(-int(b) // ps) * ps, self._max_len)

    def _buckets_below(self, bucket, floor):
        """Candidate shrink buckets strictly below `bucket`, largest
        first, each still holding `floor` total positions and each a
        bucket `_bucket_for` gives. The pow2 policy never goes below
        `bucket_pad_min` — shrinking must not mint bucket sizes normal
        admission would never produce (each would be more
        executables)."""
        if self._buckets is not None:
            cands = list(self._buckets)
        else:
            lo = max(1, int(_config.get("bucket_pad_min")))
            cands, b = [], bucket // 2
            while b >= lo:
                cands.append(b)
                b //= 2
        cands = {self._bucket_for(b) for b in cands}
        return sorted((b for b in cands if floor <= b < bucket),
                      reverse=True)

    def _chunk_of(self, width, full=False):
        """The `chunk` an executable is known by, in its jit label and on
        the spans of the steps that run it: 1 for the `slots`-wide pass,
        `prefill_chunk` (the prompt tokens a request may feed in one
        step) for the one of `2 * slots` rows, twice that for each
        further rung (a name: one value a rung, all above 1), `spec_k +
        1` for the verify pass."""
        if full:
            return self._spec_k + 1
        if width == self._slots:
            return 1
        return self._prefill_chunk * width // (2 * self._slots)

    def _runner(self, bucket, width, full=False, draft=False):
        """Step executable of `bucket` for a pass of `width` virtual
        rows: the model's `chunk_step` under jit_flat_step with the pool
        arrays donated. A bucket has the widths of the ladder
        (`_ladder`: three or four for 32 slots) and, under a drafter,
        `slots * (spec_k + 1)` with full logits, so serving compiles
        O(buckets) executables, never one per length; `_warm` compiles
        them all at the bucket's first admission."""
        key = ("chunk", bucket, width, full, draft)
        r = self._runners.get(key)
        if r is None:
            from .models._decode import jit_flat_step
            mdl = self._drafter if draft else self.model
            spec = self._draft_spec if draft else self._spec
            n_state = len(spec.streams)
            ps = self._page_size

            def step(toks, pos, slot, last, tables, flat):
                import jax.numpy as jnp
                lg, new = spec.chunk_step(toks, pos, slot, last, tables,
                                          flat, ps, full=full)
                # the token a greedy row emits is chosen where its logits
                # are (the lowest id on a tie, as np.argmax): the host
                # fetches the ids, and a row of logits only when it needs
                # one (`_fetch_out`). The ids leave the executable behind
                # the pool's arrays; `_dispatch` takes them off again
                ids = jnp.argmax(lg._data, -1).astype(jnp.int32)
                return lg, [*new, ids]

            # the label joins a device trace's instructions to this
            # program's named scopes (mx.trace.scope_map); the
            # `serve.decode_step` span's `bucket` and `chunk` name it
            r = jit_flat_step(
                mdl, step, n_state, donate_state=n_state,
                label=f"serve.paged/bucket={bucket}"
                f"/chunk={self._chunk_of(width, full)}"
                + ("/full" if full else "") + ("/draft" if draft else ""))
            self._runners[key] = r
        return r

    def _draft_runner(self, bucket):
        """Draft-chain executable: greedy proposals per dispatch on the
        drafter model, writing the pool's 'draft' stream. The chain runs
        spec_k+1 steps, not spec_k: step i writes the drafter's KV at
        position t0+i, and when the verify step accepts all k drafts
        PLUS the bonus token the next round feeds at t0+k+1 — the extra
        step fills position t0+k so the drafter cache never has a hole
        (the gap-0 sync invariant). Its proposal is discarded."""
        key = ("draft", bucket, self._spec_k)
        r = self._runners.get(key)
        if r is None:
            from .models._decode import jit_flat_step
            spec = self._draft_spec
            n_state = len(spec.streams)
            ps, k = self._page_size, self._spec_k

            def step(tok0, t0, act, tables, flat):
                return spec.draft_step(tok0, t0, act, tables, flat, ps,
                                       k + 1)

            r = jit_flat_step(self._drafter, step, n_state,
                              donate_state=n_state)
            self._runners[key] = r
        return r

    def _step_avals(self, bucket, width, tag="target"):
        """Argument avals of `bucket`'s pass of `width` virtual rows, as
        a call has them: the rows' tokens, positions and slots and each
        slot's head row (`_pack_rows`: host arrays), the page tables, the
        pool's `tag` arenas (with their placement, where they have
        one)."""
        import jax
        S = self._slots
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
        n_pg = bucket // self._page_size
        return (ints(width), ints(width), ints(width), ints(S),
                ints(1 + len(self._windows), S, n_pg) if self._windows
                else ints(S, n_pg),
                _trace.avals_of(self._pool.state[tag]))

    def lower_step(self, bucket):
        """The `jax.stages.Lowered` form of the HEAVIEST step executable
        `bucket` can run: the speculative verify pass when a drafter is
        attached (`slots * (spec_k + 1)` rows and float32 logits for
        every one of them, on the device), else the ladder's top rung —
        for ahead-of-time checks (which Pallas kernels it holds:
        chip_smoke.py reads it). Dispatches nothing."""
        if self._drafter is not None:
            width, full = self._slots * (self._spec_k + 1), True
        else:
            width, full = self._wide(), False
        return self._runner(bucket, width, full).lower(
            *self._step_avals(bucket, width))

    def _exec_peak(self, bucket):
        """AOT execution-peak bytes of the bucket's heaviest step
        executable (beyond its argument buffers; the largest over the
        target's passes) — `predict_step_bytes`-style analysis, no
        dispatch. Cached per bucket; None when the backend withholds
        analysis or refuses the build (the budget then checks resident
        bytes alone, and `_warm_seated` hears the refusal again). The
        probe adds no build: it reads the executables `_build` made,
        which are the ones the passes will call."""
        if bucket in self._exec_peaks:
            return self._exec_peaks[bucket]
        try:
            peaks = [_memsafe.compiled_exec_peak(compiled)
                     for (_, _, tag), compiled in self._build(bucket).items()
                     if tag == "target"]
            peak = None if None in peaks else max(peaks)
        except Exception:   # noqa: BLE001 — degrade to resident-only
            peak = None
        self._exec_peaks[bucket] = peak
        return peak

    def _admit_budget(self, bucket):
        """mx.memsafe budget check for admitting into `bucket`. The pool
        is the cache: one constant resident allocation made at
        construction, so admission prices the bucket's step executable's
        AOT execution peak on top of parameters and pool vs device
        capacity. Raises MemoryBudgetError on predicted overrun —
        BEFORE any page is taken or anything dispatched."""
        cap = _memsafe.capacity_bytes()
        if cap is None:
            return None
        return _memsafe.check_budget(
            f"serve.decode(bucket={bucket},slots={self._slots})",
            self._exec_peak(bucket),
            self._params_bytes + self._pool.pool_bytes(), capacity=cap)

    def _solo_overrun(self):
        """Cheap submit-time check: where parameters and pool alone pass
        the device's capacity no request can ever be admitted — reject
        it immediately with the accounting (429), instead of letting it
        age out in the queue."""
        cap = _memsafe.capacity_bytes()
        if cap is None:
            return None
        resident = self._params_bytes + self._pool.pool_bytes()
        if resident > cap:
            return (f"429 over capacity: parameters and the page pool "
                    f"need {_fmt_bytes(resident)} resident but device "
                    f"capacity is {_fmt_bytes(cap)}")
        return None

    def _admit(self):
        """Admit queued requests into free slots, oldest first (younger
        requests may pass one whose bucket group is full or over
        budget). Loops while progress is made — an evict-and-requeue
        may unblock the next pass."""
        while True:
            progress = False
            for req in list(self._queue):
                if req.state != QUEUED:
                    continue
                if self._try_admit(req):
                    progress = True
            if not progress:
                return

    def _try_admit(self, req):
        bucket = self._bucket_for(req.prompt.size + req.max_new_tokens)
        grp = self._groups.get(bucket)
        if grp is not None and grp.free_slot() is None:
            return False                     # bucket full: wait
        refusal = self._seat(req, bucket)
        return refusal is None or self._admit_pressure(req, bucket, refusal)

    def _seat(self, req, bucket, max_new=None):
        """Try to seat `req` in `bucket` — with its token budget clamped
        to `max_new`, if given: the byte budget of the bucket's step
        executable beside parameters and pool, then the request's pages.
        Every rung of the ladder comes through here. Returns None with
        the request RUNNING in its slot, or the refusal: the
        `MemoryBudgetError` (bytes; for a bucket the device itself
        refused, its error: `_warm_seated`) or a `PagesExhausted`
        (pages), with nothing taken."""
        if bucket in self._unfit:
            return self._unfit[bucket]
        try:
            self._admit_budget(bucket)
        except _memsafe.MemoryBudgetError as e:
            return e
        got = self._paged_alloc(req, bucket, max_new)
        if got is None:
            mn = req.max_new_tokens if max_new is None else max_new
            return _pages.PagesExhausted(
                -(-(req.prompt.size + mn) // self._page_size),
                self._pool.free_pages())
        if max_new is not None:
            was = req.max_new_tokens
            req.max_new_tokens = max_new
            req.degraded = f"shrink_max_new:{was}->{max_new}"
            self._note_degraded("shrink_max_new", req,
                                {"from": was, "to": max_new,
                                 "bucket": bucket})
        self._place(req, bucket, got)
        return None

    def _paged_alloc(self, req, bucket, max_new=None):
        """Match the prompt against the prefix tree and allocate the
        request's EXACT page need upfront: ceil((prompt + max_new) /
        page_size) pages, not the full bucket//page_size table. This is
        the headline memory win of paging — a 36-token request in a
        64-token bucket owns 5 pages, not 8; the executable's table is
        still bucket-wide, with unowned trailing rows padded to scratch
        page 0 (reads there are masked, and a speculative round's
        overshoot writes land in scratch instead of a live page).

        A whole-prompt match would make the first decode write land
        inside the shared last page (the re-fed prompt tail that
        produces the sampling logits), so that page is copy-on-write
        duplicated before the shared original's reference is dropped.

        Returns (pages, matched_tokens, start_pos) with one pool
        reference held per page, or None when the pool cannot cover the
        need even after evicting unreferenced prefix-tree leaves."""
        ps = self._page_size
        lp = req.prompt.size
        mn = req.max_new_tokens if max_new is None else max_new
        n_pg = min(-(-(lp + mn) // ps), bucket // ps)
        # a window class sets aside, as a count, the most the request will
        # hold in it at once; its pages are taken as the position reaches
        # them (`_window_grow`), which then cannot fail
        for w, cls in self._pool.windows.items():
            if self._window_held(w) + self._window_need(w, lp + mn) \
                    > cls.data_pages:
                return None
        # prefix sharing is declined for a model with a window class: the
        # tree would name pages of the full class only, and a hit would
        # start past rows whose window-class pages are gone
        matched_pages, matched = ([], 0) if self._windows \
            else self._tree.match(req.prompt)
        cow = matched > 0 and matched == lp
        need = (n_pg - len(matched_pages)) + (1 if cow else 0)
        if self._pool.free_pages() < need:
            self._tree.evict(need)
        if self._pool.free_pages() < need:
            for p in matched_pages:
                self._pool.decref(p)
            return None
        if cow:
            dup = self._pool.copy_page(matched_pages[-1])
            self._pool.decref(matched_pages[-1])
            matched_pages[-1] = dup
            pos0 = lp - 1
        else:
            pos0 = matched
        pages = matched_pages + self._pool.alloc(n_pg - len(matched_pages))
        return pages, matched, pos0

    def _admit_pressure(self, req, bucket, refusal):
        """The graceful-degradation ladder, walked when `_seat` refused
        (mirrors memsafe's OOM ladder): (1) shrink max_new_tokens to the
        largest smaller bucket the request can be seated in, (2)
        evict-and-requeue the youngest running request, then (3) reject
        with the accounting when no wait can help. Anything else stays
        queued. Every transition is annotated in telemetry.

        What a wait or an eviction can cure is PAGES: a drained or
        evicted request returns its exclusive pages to the pool. The
        byte budget (parameters, pool, the executable's peak) is the
        same whoever runs, so a byte refusal that shrinking did not
        cure skips rung 2 and is rejected at once.

        A REQUEUED request is never shrunk and never evicts: its client
        is mid-stream on a promised token budget (shrinking below what
        was already streamed would orphan delivered tokens), and letting
        it evict in turn would let two requests displace each other
        forever — it waits for the running work to drain instead."""
        if req.requeues == 0 and self._admit_shrunk(req, bucket):
            return True
        short_of_pages = isinstance(refusal, _pages.PagesExhausted)
        if short_of_pages and req.requeues == 0 and not req.evicted_once:
            victim = self._youngest_running(exclude=req)
            if victim is not None:
                req.evicted_once = True
                self._evict_requeue(victim, for_req=req)
                self._gc_groups()
                refusal = self._seat(req, bucket)
                if refusal is None or self._admit_shrunk(req, bucket):
                    return True
        if not short_of_pages \
                or not any(g.active() for g in self._groups.values()):
            self._queue.remove(req)
            self._finish(req, REJECTED, f"429 over capacity: {refusal}")
            return True
        return False

    def _admit_shrunk(self, req, bucket):
        """Degradation rung 1: clamp the request's token budget to the
        largest smaller bucket it can be seated in now (floored at
        serve_min_new_tokens)."""
        floor_new = max(1, min(int(_config.get("serve_min_new_tokens")),
                               req.max_new_tokens))
        for L in self._buckets_below(bucket, req.prompt.size + floor_new):
            grp = self._groups.get(L)
            if grp is not None and grp.free_slot() is None:
                continue
            if self._seat(req, L, max_new=L - req.prompt.size) is None:
                return True
        return False

    def _youngest_running(self, exclude=None):
        victim = None
        for g in self._groups.values():
            for i in g.active():
                r = g.slots[i]
                if r is exclude:
                    continue
                if victim is None or r.id > victim.id:
                    victim = r
        return victim

    def _evict_requeue(self, victim, for_req):
        """Degradation rung 2: evict the youngest running request and
        requeue it at the FRONT of the queue — its deterministic replay
        regenerates the same tokens, and `_streamed` keeps already-
        delivered ones from being re-sent."""
        self._remove_from_slots(victim)
        victim._reset_for_replay()
        self._queue.appendleft(victim)
        self._stats["requeues"] += 1
        self._note_degraded("evict_requeue", victim,
                            {"to_admit": for_req.id,
                             "streamed": victim._streamed})

    def _note_degraded(self, action, req, extra):
        self._stats["degraded"] += 1
        if _slo._enabled and req._slo_j is not None:
            _slo.note_event(req, action, **extra)
        print(f"mx.serve: degradation ladder: {action} (request "
              f"{req.id}: {extra})", file=sys.stderr)
        if _telemetry._enabled:
            _M_DEGRADED.inc()
            _telemetry.event("serve", action=action, req=req.id, **extra)
        if _diagnostics._enabled:
            _diagnostics.record_event("serve", action=action, req=req.id,
                                      **extra)

    def _place(self, req, bucket, got):
        """Seat an admitted request in its bucket group with the
        page table `_paged_alloc` built; a prefix-tree match starts the
        request at the first unmatched position — the matched prefix's
        prefill is skipped outright."""
        pages, matched, pos0 = got
        t0 = time.perf_counter()
        grp = self._groups.get(bucket)
        if grp is None:
            grp = self._groups[bucket] = _Group(
                bucket, self._slots, bucket // self._page_size,
                self._windows)
        i = grp.free_slot()
        grp.seat(i, req, pos0, pages, matched)
        for w in self._windows:
            grp.wheld[w][i] = self._window_need(
                w, req.prompt.size + req.max_new_tokens)
        self._stats["prompt_tokens"] += req.prompt.size
        self._stats["prefix_tokens"] += pos0
        if matched:
            self._stats["prefix_hits"] += 1
        self._note_admitted(req, bucket, t0)

    def _note_admitted(self, req, bucket, t0):
        try:
            self._queue.remove(req)
        except ValueError:
            pass
        req.state = RUNNING
        req._admit_perf = time.perf_counter()
        if _slo._enabled and req._slo_j is not None:
            _slo.note_admit(req, bucket)
        if _telemetry._enabled:
            _M_QWAIT.observe(req.queue_wait_s)
        if _trace._enabled:
            _trace.record_span("serve.queue_wait", req._submit_perf,
                               req._admit_perf, cat="serve", req=req.id)
            _trace.record_span("serve.admit", t0, cat="serve", req=req.id,
                               bucket=bucket)

    def _vacate(self, grp, i):
        """Release slot i of `grp`: drop one pool reference per owned
        page — tree-shared pages survive with the tree's reference,
        exclusive ones return to the free list — and, in every window
        class, the pages it holds and what admission set aside."""
        for p in grp.pages_of(i):
            self._pool.decref(p)
        for w, cls in self._pool.windows.items():
            for p in grp.window_pages_of(i, w):
                cls.decref(p)
        grp.clear(i)

    def _window_held(self, w):
        """Pages of window class `w` set aside for the seated requests."""
        return sum(sum(g.wheld[w]) for g in self._groups.values())

    def _window_grow(self, grp, feeds):
        """Before a pass: every request takes, in each window class, the
        pages the positions it is about to write reach (table entries up
        to `(p + n - 1) // page_size`). Admission set them aside."""
        ps = self._page_size
        for i, p, ids in feeds:
            hi = (p + len(ids) - 1) // ps + 1
            for w, cls in self._pool.windows.items():
                have = grp.whi[w][i]    # 0 before a request's first pass
                if hi > have:
                    grp.set_window_pages(i, w, grp.wlo[w][i], hi,
                                         cls.alloc(hi - have))

    def _window_trim(self, grp, i, pos):
        """After a pass: slot i's next row, at `pos`, sees positions above
        `pos - window` only; the pages whose every row lies at or below
        that go back to their class, and their entries to scratch."""
        for w, cls in self._pool.windows.items():
            lo, keep = grp.wlo[w][i], max(pos - w + 1, 0) // self._page_size
            if keep > lo:
                for p in grp.wtables[w][i, lo:keep].tolist():
                    cls.decref(p)
                self._stats["window_pages_freed"] += keep - lo
                grp.set_window_pages(i, w, keep, grp.whi[w][i])

    def _remove_from_slots(self, req):
        for g in self._groups.values():
            for i, r in enumerate(g.slots):
                if r is req:
                    self._vacate(g, i)
                    return True
        return False

    def _gc_groups(self):
        """Forget drained bucket groups (`buckets_allocated` names the
        live ones; the jitted runners stay cached, so re-admission into
        the bucket does not recompile)."""
        for L in [L for L, g in self._groups.items() if not g.active()]:
            del self._groups[L]

    # -- decode ----------------------------------------------------------
    def _decode_group(self, grp, sched_step, sp=None):
        """One scheduler round for a bucket group. `sp` is the step's
        live `serve.step` span, None while mx.trace is not live: the
        span sites below test it and nothing else. Mode per round:
        a SPECULATIVE round (draft chain + one verify pass of k+1 rows a
        slot) when a drafter is attached, every active slot is past its
        prompt, and at least one is greedy; otherwise a CHUNK round —
        prompt tokens of the slots still inside their prompt, one token
        for the rest, all in one pass."""
        active = grp.active()
        if not active:
            return
        all_decoding = True
        any_greedy = False
        for i in active:
            r = grp.slots[i]
            if grp.pos[i] < r.prompt.size:
                all_decoding = False
            if r.temperature == 0.0:
                any_greedy = True
        if _slo._enabled:
            for i in active:
                r = grp.slots[i]
                if r._slo_j is not None:
                    _slo.note_first_dispatch(r)
        if self._drafter is not None and all_decoding and any_greedy:
            self._spec_round(grp, active, sched_step, sp)
        else:
            self._chunk_round(grp, active, sched_step, sp)

    def _pack_rows(self, width, toks=(), pos=(), slot=(), last=None):
        """The rows of one pass as the executable takes them: `width`
        tokens, `width` positions, `width` slots, and for each slot the
        row its head reads. The fed rows come first (lists of equal
        length); the rest is padding: position -1 through slot 0 (it
        walks no page and writes that slot's scratch page), and every
        unfed slot's head on row 0."""
        pad = [0] * (width - len(toks))
        return (np.array([*toks, *pad], np.int32),
                np.array([*pos, *([-1] * len(pad))], np.int32),
                np.array([*slot, *pad], np.int32),
                np.array(last or [0] * self._slots, np.int32))

    def _feeds(self, grp, active):
        """What this step feeds, as (slot, first position, token ids):
        the last sampled token of every decoding request, then, in
        admission order, up to `prefill_chunk` prompt tokens of every
        request still inside its prompt — what a step has always owed
        each request, so none waits a step for another's prompt."""
        cap = self._prefill_chunk
        feeds, inside = [], []
        for i in active:
            r = grp.slots[i]
            p = grp.pos[i]
            if p < r.prompt.size:
                inside.append((r.id, i, p, r.prompt[p:p + cap].tolist()))
            else:
                feeds.append((i, p, [r.tokens[p - r.prompt.size]]))
        feeds.extend(feed[1:] for feed in sorted(inside))
        return feeds

    def _passes(self, feeds):
        """The step's feeds cut into passes of at most `_wide()` virtual
        rows (the ladder's top rung), in order: the fewest passes the
        ladder allows, one whenever the tokens fit the top rung (nearly
        every step), further ones when more prompt tokens are waiting
        than it holds. A request's chunk may continue in the next pass:
        its earlier tokens are in the cache by then."""
        wide = self._wide()
        passes, rows, n = [], [], 0
        for i, p, ids in feeds:
            while ids:
                take = min(len(ids), wide - n)
                rows.append((i, p, ids[:take]))
                p, ids, n = p + take, ids[take:], n + take
                if n == wide:
                    passes.append(rows)
                    rows, n = [], 0
        if rows:
            passes.append(rows)
        return passes

    def _chunk_round(self, grp, active, sched_step, sp=None):
        for feeds in self._passes(self._feeds(grp, active)):
            self._pass(grp, feeds, len(active), sched_step, sp)

    def _pass(self, grp, feeds, n_active, sched_step, sp=None):
        """One pass: pack `feeds` as virtual rows, dispatch the narrowest
        executable that holds them, and emit for every request whose
        last fed token ends or is past its prompt."""
        c0 = time.perf_counter()
        with (_trace.span("serve.prepare", cat="phase", step=sched_step,
                          slots=n_active)
              if sp else _NULLCTX) as prep:
            if self._windows:
                with self._lock:
                    self._window_grow(grp, feeds)
            toks, pos, slot, last = [], [], [], [0] * self._slots
            for i, p, ids in feeds:
                toks.extend(ids)
                pos.extend(range(p, p + len(ids)))
                slot.extend([i] * len(ids))
                last[i] = len(toks) - 1
            fed = len(toks)
            want = [i for i, p, ids in feeds
                    if self._wants_row(grp.slots[i], p + len(ids))]
            # the narrowest rung that holds them: padding rows walk no
            # page, but the matrix products see them
            width = next(w for w in self._rungs if w >= fed)
            C = self._chunk_of(width)
            run = self._runner(grp.bucket, width)
            lead = (*self._pack_rows(width, toks, pos, slot, last),
                    grp.device_tables())
            if sp:
                prep.attrs["chunk"] = C
        c1 = time.perf_counter()
        with (_trace.span("serve.decode_step", cat="serve", step=sched_step,
                          chunk=C, bucket=grp.bucket, slots=n_active,
                          width=width, fed=fed)
              if sp else _NULLCTX):
            with (_trace.span("serve.dispatch", cat="phase",
                              step=sched_step, chunk=C, width=width)
                  if sp else _NULLCTX):
                out = self._dispatch(grp, run, lead, "target")
                if self._drafter is not None:
                    # mirror the pass on the drafter so its cache tracks
                    # the target position-for-position (gap-0: a later
                    # speculative round can start its chain with no
                    # catch-up work)
                    drun = self._runner(grp.bucket, width, draft=True)
                    self._dispatch(grp, drun, lead, "draft")
            c2 = time.perf_counter()
            chosen, rows = self._fetch_out(out, want, sched_step, C, sp)
            c3 = time.perf_counter()
        with (_trace.span("serve.stream", cat="serve", step=sched_step)
              if sp else _NULLCTX) as stream:
            if sp:
                tokens0 = self._stats["tokens"]
            with self._lock:
                self._stats["steps"] += 1
                self._stats["chunk_dispatches"] += 1
                self._stats["chunk_steps" if C > 1 else "token_steps"] += 1
                self._note_pass(width, fed)
                for i, p, ids in feeds:
                    r = grp.slots[i]
                    if r is None or r.state in TERMINAL:
                        continue    # evicted/cancelled under the dispatch
                    ni = len(ids)
                    grp.pos[i] = p + ni
                    self._note_fed(p, ni)
                    if self._windows:
                        self._window_trim(grp, i, p + ni)
                    lp = r.prompt.size
                    if p + ni >= lp and not grp.inserted[i] \
                            and not self._windows:
                        self._tree_insert(grp, i, r)
                    if p + ni < lp:
                        continue    # still prefilling the prompt
                    nxt = self._emit(r, chosen[i], rows.get(i))
                    if (r.eos is not None and nxt == r.eos) \
                            or len(r.tokens) >= r.max_new_tokens:
                        self._vacate(grp, i)
                        self._finish(r, DONE, "200 ok")
            if sp:
                _close_round(sp, stream, C, self._stats["tokens"] - tokens0)
        self._note_row(c0, c1, c2, c3, time.perf_counter(), fed, width,
                       len(feeds))

    def _spec_round(self, grp, active, sched_step, sp=None):
        """One speculative decoding round: the drafter chains k greedy
        proposals per eligible slot, the target verifies them all in ONE
        pass of k+1 virtual rows a slot (the head on every row), and the
        host keeps, comparing the drafts with that pass's token ids, the
        longest agreeing prefix plus the bonus token — exact greedy
        acceptance, so the emitted stream is plain greedy decode's.
        Non-greedy slots ride along with a single ordinary token, the
        rest of their rows padding; their row of logits, and a
        `keep_logits` slot's rows, come over as in any pass
        (`_fetch_out`)."""
        import jax.numpy as jnp
        k = self._spec_k
        S = self._slots
        width = S * (k + 1)
        c0 = time.perf_counter()
        with (_trace.span("serve.prepare", cat="phase", step=sched_step,
                          chunk=k + 1, slots=len(active))
              if sp else _NULLCTX):
            tok0 = np.zeros((S,), np.int32)
            t0 = np.zeros((S,), np.int32)
            spec_row = np.zeros((S,), bool)
            n = np.zeros((S,), np.int32)
            for i in active:
                r = grp.slots[i]
                p = grp.pos[i]
                tok0[i] = r.tokens[p - r.prompt.size]
                t0[i] = p
                spec_row[i] = r.temperature == 0.0
            tables_d = grp.device_tables()
            drafts_out = self._dispatch(
                grp, self._draft_runner(grp.bucket),
                (jnp.asarray(tok0), jnp.asarray(t0), jnp.asarray(spec_row),
                 tables_d), "draft")
            # (B, k+1): the chain's last proposal only fills the cache
            drafts = _fetch(drafts_out, np.int32, self._row)
            # slot-major: rows i*(k+1) .. of slot i, what it does not feed
            # padding through its own scratch page
            toks = np.zeros((width,), np.int32)
            pos = np.full((width,), -1, np.int32)
            slot = np.repeat(np.arange(S, dtype=np.int32), k + 1)
            for i in active:
                w = i * (k + 1)
                n[i] = k + 1 if spec_row[i] else 1
                toks[w] = tok0[i]
                if spec_row[i]:
                    toks[w + 1:w + k + 1] = drafts[i, :k]
                pos[w:w + n[i]] = t0[i] + np.arange(n[i])
            fed = int(n.sum())
            want = [i * (k + 1) + j for i in active
                    if self._wants_row(grp.slots[i]) for j in range(n[i])]
            run = self._runner(grp.bucket, width, True)
        c1 = time.perf_counter()
        with (_trace.span("serve.decode_step", cat="serve", step=sched_step,
                          chunk=k + 1, bucket=grp.bucket,
                          slots=len(active), spec_k=k, width=width, fed=fed)
              if sp else _NULLCTX):
            # `full`: the head runs on every row, no slot's head row
            with (_trace.span("serve.dispatch", cat="phase",
                              step=sched_step, chunk=k + 1, width=width)
                  if sp else _NULLCTX):
                out = self._dispatch(
                    grp, run, (toks, pos, slot, np.zeros((S,), np.int32),
                               tables_d), "target")
            c2 = time.perf_counter()
            chosen, rows = self._fetch_out(out, want, sched_step, k + 1, sp)
            c3 = time.perf_counter()
            chosen = chosen.reshape(S, k + 1)
        with (_trace.span("serve.stream", cat="serve", step=sched_step)
              if sp else _NULLCTX) as stream:
            if sp:
                tokens0 = self._stats["tokens"]
            with self._lock:
                self._stats["steps"] += 1
                self._stats["spec_rounds"] += 1
                self._note_pass(width, fed)
                self._stats["fetched_bytes"] += drafts.nbytes
                for i in active:
                    r = grp.slots[i]
                    if r is None or r.state in TERMINAL:
                        continue
                    p = grp.pos[i]
                    self._note_fed(p, int(n[i]))
                    w = i * (k + 1)
                    if not spec_row[i]:
                        grp.pos[i] = p + 1
                        nxt = self._emit(r, chosen[i, 0], rows.get(w))
                        if (r.eos is not None and nxt == r.eos) \
                                or len(r.tokens) >= r.max_new_tokens:
                            self._vacate(grp, i)
                            self._finish(r, DONE, "200 ok")
                        continue
                    self._stats["drafts_proposed"] += k
                    emitted = 0
                    done = False
                    for j in range(k + 1):
                        # the target's own choice for the row: exact
                        # acceptance means verify-then-keep, never trust
                        nxt = self._emit(r, chosen[i, j], rows.get(w + j))
                        emitted += 1
                        if (r.eos is not None and nxt == r.eos) \
                                or len(r.tokens) >= r.max_new_tokens:
                            done = True
                            break
                        if j >= k or int(drafts[i, j]) != nxt:
                            break
                        self._stats["drafts_accepted"] += 1
                    grp.pos[i] = p + emitted
                    if done:
                        self._vacate(grp, i)
                        self._finish(r, DONE, "200 ok")
            if sp:
                _close_round(sp, stream, k + 1,
                             self._stats["tokens"] - tokens0)
        self._note_row(c0, c1, c2, c3, time.perf_counter(), fed, width,
                       len(active))

    def _tree_insert(self, grp, i, req):
        """One-time prefix-tree registration of a slot's fully-prefilled
        prompt blocks (whole pages only — the partial tail stays
        exclusively owned, and decode writes only land at positions past
        the prompt, so registered pages are immutable from here on)."""
        lp = req.prompt.size
        self._tree.insert(req.prompt,
                          grp.pages_of(i)[:lp // self._page_size])
        grp.inserted[i] = True

    def _dispatch(self, grp, run, lead, tag):
        """One batched dispatch under the transient-fault RetryPolicy,
        threading the pool's `tag` page-array stream through the donated
        state. Donated-buffer safety: a failure that consumed the
        donated arenas cannot be retried in place — that is re-raised
        (non-retryable) instead of computing garbage."""
        pool = self._pool

        def call():
            c0 = pool.state[tag][0]
            if hasattr(c0, "is_deleted") and c0.is_deleted():
                raise RuntimeError(
                    "mx.serve: the failed dispatch consumed the donated "
                    f"page-pool buffers ('{tag}' stream) — cannot retry "
                    f"in place (bucket {grp.bucket})")
            n = len(pool.state[tag])
            out, new_state = run(*lead, pool.state[tag])
            pool.state[tag] = new_state[:n]
            # a chunk pass returns its rows' token ids behind the arrays
            return (out, *new_state[n:]) if len(new_state) > n else out

        def on_retry(exc, attempt, delay):
            with self._lock:
                self._stats["retries"] += 1
                if _slo._enabled:
                    for i in grp.active():
                        r = grp.slots[i]
                        if r is not None and r._slo_j is not None:
                            _slo.note_event(r, "retry", attempt=attempt,
                                            error=type(exc).__name__)
            print(f"mx.serve: retrying decode dispatch after "
                  f"{type(exc).__name__}: {exc} (attempt {attempt + 2}/"
                  f"{self._retry.max_attempts}, backoff {delay:.2f}s)",
                  file=sys.stderr)

        return self._retry.call(call, site="serve-dispatch",
                                abort=self._stop.is_set,
                                on_retry=on_retry)

    def _sample(self, req, lg):
        """Next token of a request with `temperature > 0` from its logits
        row: top-k softmax sampling from the request's own seeded rng, on
        the host, so its stream is deterministic and independent of what
        else shares the batch."""
        if req._rng is None:
            req._rng = np.random.RandomState(req.seed)
        if req.top_k:
            kth = np.partition(lg, -req.top_k)[-req.top_k]
            lg = np.where(lg < kth, -np.inf, lg)
        lg = lg / req.temperature
        p = np.exp(lg - lg.max())
        p /= p.sum()
        return int(req._rng.choice(p.size, p=p))

    @staticmethod
    def _wants_row(req, pos=None):
        """True when the host needs the float32 logits of the row `req`
        emits from: to sample (`temperature > 0`) or to keep
        (`keep_logits`). A greedy request that keeps nothing needs the
        row's token id alone. `pos`: where the pass leaves the request;
        inside its prompt it emits nothing."""
        if req is None or (pos is not None and pos < req.prompt.size):
            return False
        return req.temperature > 0.0 or req.logits is not None

    def _fetch_out(self, out, want, sched_step, chunk, sp):
        """What the host needs of a pass's output `(logits, ids)`, both on
        the device: the int32 ids of all its rows (4 bytes a row; the wait
        for them is the step's fence) and, of the float32 logits, the rows
        `want` names and no others, gathered on the device and brought
        in one copy (`_take_rows`). Returns (ids, {row: its logits})."""
        with (_trace.span("serve.fetch", cat="phase", step=sched_step,
                          chunk=chunk) if sp else _NULLCTX) as span:
            logits, ids = out
            if want:
                picked = _take_rows(logits, want)   # queued behind the step
                picked.copy_to_host_async()         # and its copy behind it
            ids = _fetch(ids, np.int32, self._row)
            rows, nbytes = {}, ids.nbytes
            if want:
                picked = _fetch(picked, np.float32, self._row)
                rows = dict(zip(want, picked))
                nbytes += picked.nbytes
            if sp:
                span.attrs.update(bytes=nbytes, rows=len(want))
        with self._lock:
            self._stats["fetched_bytes"] += nbytes
            self._stats["logit_rows_fetched"] += len(want)
        return ids, rows

    def _note_row(self, c0, c1, c2, c3, c4, fed, width, walks):
        """Add one pass to the step's row of the step table: its prepare
        (c0-c1), dispatch (c1-c2), fetch (c2-c3) and stream (c3-c4) on
        the clock, the tokens fed, the runs of one request's rows they
        came in (a feed of `_pass`, a slot of a speculative round: each
        one walk of the request's pages in the paged kernel), and whether
        it was wider than `slots` rows."""
        row = self._row
        row[_R_PREP] += c1 - c0
        row[_R_DISP] += c2 - c1
        row[_R_FETCH] += c3 - c2
        row[_R_STREAM] += c4 - c3
        row[_R_PASSES] += 1
        row[_R_FED] += fed
        row[_R_WALKS] += walks
        if width > self._slots:
            row[_R_WIDE] = 1

    def _note_pass(self, width, fed):
        """Count one pass: its virtual rows, the tokens fed in them (the
        rest was padding), and the dispatch under its width."""
        self._stats["rows_dispatched"] += width
        self._stats["rows_fed"] += fed
        self._width_dispatches[width] += 1

    def _note_fed(self, p, ni):
        """Count `ni` tokens fed at positions p..: the token at position q
        attends a context of q + 1 tokens, of which a learned sparse
        attention keeps `index_topk`. Positions only; no device read."""
        st, k = self._stats, self._spec.index_topk
        ctx = ni * p + ni * (ni + 1) // 2
        st["attn_tokens"] += ni
        st["attn_ctx_tokens"] += ctx
        for w in self._windows:
            # the contexts cut to the window: min(q + 1, w) over the rows
            under = max(0, min(ni, w - p))
            st["attn_window_tokens"] += \
                under * p + under * (under + 1) // 2 + (ni - under) * w
        if k is None or p + ni <= k:
            st["attn_sel_tokens"] += ctx
        else:
            over = min(ni, p + ni - k)      # the last `over` contexts pass k
            under = ni - over
            st["attn_sel_tokens"] += under * p + under * (under + 1) // 2 \
                + over * k
            st["sparse_tokens"] += over

    def _emit(self, req, tok, row):
        """`req` emits the token of one row of a pass and returns it:
        `tok`, the row's argmax as the device took it, unless the request
        samples, which it does from `row`, the row's logits (fetched for a
        request that samples or keeps them, None for every other)."""
        if req.temperature > 0.0:
            tok = self._sample(req, row)
        else:
            tok = int(tok)
            self._stats["rows_sampled_on_device"] += 1
        req.tokens.append(tok)
        if req.logits is not None:
            req.logits.append(np.array(row, np.float32))
        self._stats["tokens"] += 1
        if _telemetry._enabled:
            _M_TOKENS.inc()
        if len(req.tokens) > req._streamed:
            req._streamed = len(req.tokens)
            if _slo._enabled and req._slo_j is not None:
                _slo.note_token(req)
            if req._first_token_perf is None:
                req._first_token_perf = time.perf_counter()
                if _telemetry._enabled:
                    _M_TTFT.observe(req.ttft_s)
            req._stream_q.put(tok)
        return tok

    # -- terminal transitions -------------------------------------------
    _OUTCOME = {DONE: "completed", REJECTED: "rejected", SHED: "shed",
                EXPIRED: "expired", CANCELLED: "cancelled",
                FAILED: "failed"}

    def _finish(self, req, state, verdict):
        if req.state in TERMINAL:
            return
        req.state = state
        req.verdict = verdict
        req._finish_perf = time.perf_counter()
        # terminal requests leave the id table — a long-running server
        # must not grow RSS with every request it ever answered (the
        # caller keeps its own Request reference; cancel-by-id only ever
        # targets live requests)
        self._by_id.pop(req.id, None)
        self._stats[self._OUTCOME[state]] += 1
        if state != DONE:
            print(f"mx.serve: request {req.id}: {verdict}",
                  file=sys.stderr)
        if _telemetry._enabled:
            _M_REQUESTS.labels(outcome=self._OUTCOME[state]).inc()
            if state != DONE:
                _telemetry.event("serve", action="finish", req=req.id,
                                 state=state, verdict=verdict)
        if _slo._enabled and req._slo_j is not None:
            _slo.note_finish(req, self._OUTCOME[state], verdict)
        req._stream_q.put(_EOS_SENTINEL)
        req._done.set()


if _config.get("serve"):
    enable()
