"""mx.dataflow — the input-to-device performance layer.

The reference framework hid host-side input work behind device compute with
an async `PrefetcherIter` (`src/io/iter_prefetcher.h`, SURVEY §2.1): a
background thread stages the *next* batches while the current one trains.
The TPU-native equivalent staged here is stronger — batches are not just
decoded ahead of time, they are already mesh-sharded `jax.Array`s by the
time the train step sees them, so the H2D transfer itself overlaps device
compute instead of serializing with it:

  * `prefetch_to_mesh(it, trainer, depth=2)` — background thread converts
    host batches (numpy / NDArray trees) into sharded device arrays for the
    next `depth` steps using the trainer's own batch shardings (including
    data_specs/label_specs overrides); worker exceptions surface at
    `next()` with their original traceback; the thread shuts down cleanly
    on close()/GC/partial iteration.
  * `BucketPad(axis_buckets=...)` — pads varlen batches up to configured or
    power-of-two buckets (pairing each pad with a valid-length input) so a
    stream of novel sequence lengths compiles a handful of executables
    instead of one per length.
  * `ensure_compile_cache()` — turns on jax's persistent XLA compilation
    cache at first trainer construction (`JAX_COMPILATION_CACHE_DIR` when
    set, else the fixed `<checkout>/.jax_cache`), so relaunches skip cold
    compiles entirely.

Telemetry (all series degrade to a module-bool check when disabled):
`dataloader_prefetch_depth{stage="device"}` (staged-batch depth, distinct
from the host DataLoader's series so input-stall attribution can name the
bottleneck stage), `device_prefetch_wait_seconds` (consumer blocked on
staging), `h2d_bytes_total` (payload staged onto the mesh), and
`bucket_pad_waste_ratio` (padding overhead next to the recompiles it
eliminates).
"""
from __future__ import annotations

import contextlib
import math
import os
import queue
import sys
import threading
import time

import numpy as np

from . import _locklint
from . import config as _config
from . import goodput as _goodput
from . import guard as _guard
from . import resilience as _resilience
from . import telemetry as _telemetry
from . import trace as _trace

_NULLCTX = contextlib.nullcontext()

__all__ = ["prefetch_to_mesh", "MeshPrefetcher", "BucketPad",
           "bucket_length", "ensure_compile_cache", "autofit",
           "AutofitResult"]

_M_DEPTH = _telemetry.gauge(
    "dataloader_prefetch_depth", "batches buffered ahead of the consumer "
    "(0 while the consumer is starved = input-bound); fanned out by stage: "
    "host (DataLoader worker batches) vs device (mesh-staged arrays)")
_M_STAGE_WAIT = _telemetry.histogram(
    "device_prefetch_wait_seconds", "time the training loop spent blocked "
    "waiting for a mesh-staged batch — the H2D-staging share of the input "
    "stall (compare dataloader_wait_seconds for the host-batch share)")
_M_H2D_BYTES = _telemetry.counter(
    "h2d_bytes_total", "payload bytes staged host-to-device by "
    "prefetch_to_mesh")
_M_PAD_WASTE = _telemetry.histogram(
    "bucket_pad_waste_ratio", "fraction of each BucketPad-padded batch "
    "that is padding (0 = exact bucket fit) — the overhead bought to "
    "bound the jit-cache population",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0))
_M_CACHE_HITS = _telemetry.counter(
    "compile_cache_hits_total", "compiles served from the persistent XLA "
    "compilation cache (warm: deserialized, not rebuilt)")
_M_CACHE_MISSES = _telemetry.counter(
    "compile_cache_misses_total", "compiles the persistent cache could not "
    "serve (cold: full XLA compile, then written back)")


# ---------------------------------------------------------------------------
# tree helpers (nested tuple/list/dict/namedtuple batches of NDArray /
# numpy / jax arrays — jax.tree_util preserves the node types exactly, and
# NDArray, being unregistered, is a leaf)
# ---------------------------------------------------------------------------

def _raw(leaf):
    """Strip an NDArray wrapper down to its jax/numpy payload."""
    from .ndarray import NDArray
    if isinstance(leaf, NDArray):
        return leaf._data
    return leaf


# ---------------------------------------------------------------------------
# prefetch_to_mesh
# ---------------------------------------------------------------------------

class _WorkerExit(Exception):
    """Internal: the prefetcher was closed under the worker."""


_STOP = object()


class MeshPrefetcher:
    """Background-staged iterator: host batches in, mesh-sharded device
    batches out, `depth` steps ahead of the consumer.

    `shardings` may be a ShardedTrainer (its `_batch_shardings` — including
    data_specs/label_specs overrides — decide placement; batches must then
    be `(data, labels)` pairs), an explicit list of `jax.sharding.Sharding`
    per leaf, or None (plain committed default-device placement — the eager
    gluon/Estimator path). `transform` (e.g. a BucketPad) runs inside the
    worker thread so host-side padding overlaps device compute too."""

    def __init__(self, iterator, shardings=None, depth=2, transform=None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._q = queue.Queue(maxsize=depth)
        self._closed = threading.Event()
        self._exhausted = False
        # close() is idempotent and may be called concurrently — including
        # from a SIGTERM/preemption path re-entering while the first close
        # is mid-join — so its bookkeeping sits behind an RLock
        self._close_lock = _locklint.make_rlock("dataflow.prefetcher.close")
        self._close_done = False
        # the worker closes over locals (not self) so a consumer dropping
        # its last reference lets __del__ run while the thread is alive
        closed, q = self._closed, self._q
        stage = _Stager(shardings)
        source = iter(iterator)
        policy_cell = [None]   # RetryPolicy built once, on first enabled use

        def _worker():
            try:
                for item in source:
                    if closed.is_set():
                        return
                    if transform is not None:
                        item = transform(item)
                    staged = _stage_resilient(stage, item, closed,
                                              policy_cell)
                    _q_put(q, staged, closed)
                _q_put(q, _STOP, closed)
            except _WorkerExit:
                return
            except BaseException as e:   # noqa: BLE001 — relayed to consumer
                try:
                    _q_put(q, e, closed)
                except _WorkerExit:
                    return

        self._thread = threading.Thread(
            target=_worker, name="mx-dataflow-prefetch", daemon=True)
        self._thread.start()

    # -- consumer side --------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted or self._closed.is_set():
            raise StopIteration
        tr = _trace.live()
        if _telemetry._enabled or tr or _goodput._enabled:
            t0 = time.perf_counter()
            # the consumer-visible input stall: how long the train loop
            # sat blocked waiting for a mesh-staged batch — the span
            # trace_report's input-bound verdict sums
            with (_trace.span("input.batch_wait", cat="input")
                  if tr else _NULLCTX) as wait:
                item = self._q.get()
                stalled = item is not _STOP \
                    and not isinstance(item, BaseException)
                if tr:
                    wait.keep = stalled
            if stalled:
                # waits that produced a batch are the H2D-staging stall;
                # waiting for the end-of-stream marker is not a stall
                t1 = time.perf_counter()
                if _telemetry._enabled:
                    _M_STAGE_WAIT.observe(t1 - t0)
                    _M_DEPTH.labels(stage="device").set(self._q.qsize())
                if _goodput._enabled:
                    # the same consumer-visible wait, accounted as
                    # badput:input_stall wall-clock
                    _goodput.note("input_stall", t0, t1)
        else:
            item = self._q.get()
        if item is _STOP:
            self._exhausted = True
            self._thread.join()
            raise StopIteration
        if isinstance(item, BaseException):
            self._exhausted = True
            self._thread.join()
            # re-raise the worker's exception object: its __traceback__
            # still points at the failing frame inside the worker
            raise item
        return item

    def close(self):
        """Stop the worker and release the staged batches. Idempotent and
        thread-safe — callable again from a SIGTERM/preemption path while
        a worker is mid-`device_put` (the in-flight transfer completes,
        its result is drained, the worker exits at the next bounded put).
        Called by __del__ and __exit__, safe mid-iteration. A worker
        blocked INSIDE the source iterator's next() cannot be interrupted
        (no thread cancellation in Python) — it is abandoned as a daemon
        and exits at the source's next yield; the join timeout bounds how
        long close() waits for that."""
        with self._close_lock:
            if self._close_done:
                return
            self._closed.set()
            # drain so a worker blocked on put() observes the close promptly
            self._drain()
            if self._thread is not threading.current_thread():
                self._thread.join(timeout=5)
            # a put already in flight during the first drain can land in the
            # emptied queue; drain again after the join so close() really
            # does release every staged device batch
            self._drain()
            # only a confirmed-dead worker makes close() a no-op next time:
            # a timed-out join leaves it retryable
            if not self._thread.is_alive():
                self._close_done = True

    def _drain(self):
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _stage_resilient(stage, item, closed, policy_cell):
    """One batch through the stager. With mx.resilience enabled, the
    `stall_input` fault point fires here and transient staging failures
    (OSError/ConnectionError/TimeoutError — e.g. a flaky remote
    filesystem feeding device_put) retry under the configured
    RetryPolicy. The policy is built ONCE per prefetcher (policy_cell) —
    not per batch, this is the input hot path — and retries abort early
    if the prefetcher closes underneath. Disabled: one bool check, then
    the plain call."""
    if _guard._enabled:
        # mx.guard liveness from the input worker: a trainer blocked on
        # a slow input queue still shows a fresh beat (phase=input), so
        # the supervisor distinguishes "starving" from "dead" — the
        # in-memory record updates every batch, the file write stays
        # rate-limited
        _guard.heartbeat(phase="input")
    if not _resilience._enabled:
        return stage(item)
    _resilience.fault_point("input")
    if policy_cell[0] is None:
        policy_cell[0] = _resilience.RetryPolicy()
    return policy_cell[0].call(
        stage, item, site="prefetch-stage", abort=closed.is_set)


def _q_put(q, item, closed):
    """Bounded put that aborts when the prefetcher closes underneath the
    worker (the consumer stopped iterating; blocking forever would leak
    the thread)."""
    while not closed.is_set():
        try:
            q.put(item, timeout=0.05)
            return
        except queue.Full:
            continue
    raise _WorkerExit


class _Stager:
    """Per-batch host->mesh staging: flatten the batch tree, device_put
    every leaf with its target sharding (one batched transfer), rebuild
    the tree as NDArrays."""

    def __init__(self, shardings):
        self._shardings = shardings

    def __call__(self, item):
        import jax

        from .ndarray import NDArray

        # producer-side H2D staging (runs in the prefetch worker thread,
        # overlapped with device compute — a long span here that never
        # surfaces as batch_wait means the overlap worked)
        with (_trace.span("input.h2d_stage", cat="input")
              if _trace.live() else _NULLCTX):
            leaves, treedef = jax.tree_util.tree_flatten(
                item, is_leaf=lambda x: isinstance(x, NDArray))
            raw = [_raw(x) for x in leaves]
            targets = self._targets(item, raw)
            if _telemetry._enabled:
                moved = 0
                for r, s in zip(raw, targets or [None] * len(raw)):
                    if isinstance(r, np.ndarray):
                        moved += r.nbytes
                    elif s is not None and getattr(r, "sharding", None) != s:
                        moved += getattr(r, "nbytes", 0)
                if moved:
                    _M_H2D_BYTES.inc(moved)
            if targets is None:
                staged = [jax.device_put(r) for r in raw]
            else:
                staged = [r if getattr(r, "sharding", None) == t
                          else jax.device_put(r, t)
                          for r, t in zip(raw, targets)]
            out = jax.tree_util.tree_unflatten(
                treedef, [NDArray(s) for s in staged])
        return out

    def _targets(self, item, raw):
        sh = self._shardings
        if sh is None:
            return None
        if isinstance(sh, (list, tuple)):
            if len(sh) != len(raw):
                raise ValueError(
                    f"got {len(sh)} shardings for a batch of {len(raw)} "
                    "arrays")
            return list(sh)
        # a ShardedTrainer (or anything exposing _batch_shardings): batches
        # are (data, labels) pairs; count leaves on each side
        if hasattr(sh, "_batch_shardings"):
            if not (isinstance(item, (tuple, list)) and len(item) == 2):
                n_data, n_label = len(raw), 0
            else:
                import jax

                from .ndarray import NDArray
                n_data = len(jax.tree_util.tree_leaves(
                    item[0], is_leaf=lambda x: isinstance(x, NDArray)))
                n_label = len(raw) - n_data
            shapes = tuple(tuple(getattr(r, "shape", ())) for r in raw)
            return list(sh._batch_shardings(n_data, n_label, shapes))
        raise TypeError(
            "shardings must be None, a list of jax shardings, or a trainer "
            f"with _batch_shardings; got {type(sh).__name__}")


def prefetch_to_mesh(iterator, trainer_or_shardings=None, depth=None,
                     transform=None):
    """Stage batches onto the mesh `depth` steps ahead of the consumer.

    Wrap any host batch iterator (a gluon DataLoader, a generator of
    `(data, labels)` pairs) and iterate the result instead: a background
    thread converts each batch into mesh-sharded device arrays while the
    current step runs, so H2D transfer overlaps compute. Pass the
    ShardedTrainer to reuse its batch shardings (data_specs/label_specs
    included), an explicit sharding list, or None for default-device
    placement (the eager gluon path). `transform` (e.g. `BucketPad`) runs
    in the worker thread. Close via `close()`, a `with` block, or just
    dropping the iterator; worker exceptions re-raise at `next()` with
    their original traceback."""
    if depth is None:
        depth = _config.get("device_prefetch_depth") or 2
    return MeshPrefetcher(iterator, trainer_or_shardings, depth=depth,
                          transform=transform)


# ---------------------------------------------------------------------------
# shape bucketing
# ---------------------------------------------------------------------------

def bucket_length(length, buckets="pow2", floor=None):
    """The bucket a raw length rounds up to — the ONE bucketing policy
    shared by `BucketPad` (varlen batch axes) and `mx.serve` (KV-cache
    lengths), so the two subsystems can never bucket the same stream
    differently. `buckets` is a sorted sequence of sizes or \"pow2\"
    (next power of two, floored at `floor` — default the
    `bucket_pad_min` knob). Lengths above the largest configured bucket
    keep their raw size (one compile per such outlier, same as
    unbucketed)."""
    length = int(length)
    if buckets == "pow2":
        if floor is None:
            floor = max(1, int(_config.get("bucket_pad_min")))
        return max(int(floor),
                   1 << max(0, math.ceil(math.log2(max(length, 1)))))
    for b in buckets:
        if b >= length:
            return int(b)
    return length


class BucketPad:
    """Pad varlen batches up to configured (or power-of-two) buckets so a
    stream of novel raw lengths compiles a bounded set of step executables.

    axis_buckets: {axis: buckets} where buckets is a sorted sequence of
    sizes or the string "pow2" (next power of two, floored at the
    `bucket_pad_min` knob). Default: {1: "pow2"} — the sequence axis.
    Lengths above the largest configured bucket keep their raw size (a
    compile per such outlier, same as unbucketed).

    Each padded DATA array is paired with a valid-length input (int32,
    shape (batch,), the raw length) appended to the data list, so masked
    models/losses can ignore the pad; pass append_valid_length=False for
    workloads (e.g. BERT) whose batch already carries one. Labels are
    padded along the same axes with `label_pad_value` but never grow a
    valid-length input.

    Use per batch (`bp((data, labels))`), over an iterator (`bp.wrap(it)`),
    or as `prefetch_to_mesh(..., transform=bp)` — there the padding happens
    in the prefetch worker thread and overlaps device compute."""

    def __init__(self, axis_buckets=None, pad_value=0, label_pad_value=0,
                 append_valid_length=True):
        self.axis_buckets = dict(axis_buckets) if axis_buckets else {1: "pow2"}
        for axis, buckets in self.axis_buckets.items():
            if buckets != "pow2":
                bl = sorted(int(b) for b in buckets)
                if not bl:
                    raise ValueError(f"axis {axis}: empty bucket list")
                self.axis_buckets[axis] = bl
        self.pad_value = pad_value
        self.label_pad_value = label_pad_value
        self.append_valid_length = append_valid_length

    def _bucket(self, length, buckets):
        return bucket_length(length, buckets)

    def _pad_leaf(self, leaf, pad_value, collect_valid):
        arr = _raw(leaf)
        padded = arr
        raw_elems = int(np.prod(arr.shape)) if arr.ndim else 1
        valid = None
        pads = [(0, 0)] * arr.ndim
        grew = False
        for axis, buckets in self.axis_buckets.items():
            if axis >= arr.ndim:
                continue
            length = arr.shape[axis]
            target = self._bucket(length, buckets)
            if target > length:
                pads[axis] = (0, target - length)
                grew = True
            if collect_valid and valid is None:
                valid = np.full(arr.shape[0] if arr.ndim else 1, length,
                                dtype=np.int32)
        if grew:
            host = np.asarray(arr)
            padded = np.pad(host, pads, constant_values=pad_value)
            if _telemetry._enabled:
                _M_PAD_WASTE.observe(
                    1.0 - raw_elems / max(int(np.prod(padded.shape)), 1))
        elif _telemetry._enabled and any(
                ax < arr.ndim for ax in self.axis_buckets):
            _M_PAD_WASTE.observe(0.0)
        return padded, (valid if grew or collect_valid else None)

    def _pad_side(self, side, pad_value, collect_valid):
        single = not isinstance(side, (list, tuple))
        items = [side] if single else list(side)
        out, valids = [], []
        for leaf in items:
            padded, valid = self._pad_leaf(leaf, pad_value, collect_valid)
            out.append(padded)
            if valid is not None:
                valids.append(valid)
        if collect_valid:
            out.extend(valids)
            return out
        return out[0] if single else out

    def __call__(self, batch):
        """One batch: a `(data, labels)` pair, or a bare data array/list."""
        if isinstance(batch, tuple) and len(batch) == 2 and any(
                isinstance(s, (list, tuple)) or hasattr(_raw(s), "ndim")
                for s in batch):
            data, labels = batch
            data = self._pad_side(data, self.pad_value,
                                  self.append_valid_length)
            labels = self._pad_side(labels, self.label_pad_value, False)
            return (data, labels)
        return self._pad_side(batch, self.pad_value, self.append_valid_length)

    def wrap(self, iterator):
        """Generator applying the pad to every batch of `iterator`."""
        for batch in iterator:
            yield self(batch)


# ---------------------------------------------------------------------------
# auto-fit: the largest batch/bucket configuration that fits the device
# ---------------------------------------------------------------------------


class AutofitResult:
    """What `autofit` chose and how it got there.

    Fields: `batch_size` (largest fitting global batch), `predicted_bytes`
    / `exec_peak_bytes` / `resident_bytes` (the chosen config's plan),
    `capacity_bytes`, `headroom_bytes`, `buckets` (the BucketPad
    boundaries that fit at the chosen batch, when bucket lengths were
    probed), `next_larger` ({"batch_size", "predicted_bytes"} of the
    smallest probed config that did NOT fit — None when the search was
    capped by max_batch), and `probes` (every AOT plan, in probe order).
    `bucket_pad(**kwargs)` builds the matching BucketPad; feed
    `batch_size` straight into the data pipeline and train."""

    def __init__(self, batch_size, plan, capacity_bytes, probes,
                 buckets=None, next_larger=None):
        self.batch_size = batch_size
        self.predicted_bytes = plan["predicted_bytes"]
        self.exec_peak_bytes = plan["exec_peak_bytes"]
        self.resident_bytes = plan["resident_bytes"]
        self.capacity_bytes = capacity_bytes
        self.headroom_bytes = capacity_bytes - plan["predicted_bytes"]
        self.buckets = list(buckets) if buckets is not None else None
        self.next_larger = next_larger
        self.probes = list(probes)

    def bucket_pad(self, axis=1, **kwargs):
        """A BucketPad over the bucket boundaries that fit (only when
        autofit probed buckets)."""
        if not self.buckets:
            raise ValueError("autofit ran without bucket candidates — "
                             "pass buckets=[...] to probe them")
        return BucketPad(axis_buckets={axis: list(self.buckets)}, **kwargs)

    def as_dict(self):
        return {
            "batch_size": self.batch_size,
            "predicted_bytes": self.predicted_bytes,
            "exec_peak_bytes": self.exec_peak_bytes,
            "resident_bytes": self.resident_bytes,
            "capacity_bytes": self.capacity_bytes,
            "headroom_bytes": self.headroom_bytes,
            "buckets": self.buckets,
            "next_larger": self.next_larger,
            "probes": self.probes,
        }

    def __repr__(self):
        extra = f", buckets={self.buckets}" if self.buckets else ""
        return (f"AutofitResult(batch_size={self.batch_size}, "
                f"predicted={self.predicted_bytes}, "
                f"capacity={self.capacity_bytes}{extra})")


def autofit(trainer, make_batch, max_batch=1024, capacity=None,
            buckets=None, multiple_of=None, verbose=True):
    """Binary-search the largest batch size (and optionally the BucketPad
    bucket boundaries) whose PREDICTED train-step peak fits the device —
    AOT lowering + XLA memory_analysis only, no device step executes and
    no batch transfers (mx.memsafe, "Memory Safe Computations with XLA").

    `make_batch(batch_size)` (or `make_batch(batch_size, seq_len)` when
    `buckets` is given) returns one `(data, labels)` host batch — numpy /
    NDArray; only shapes and dtypes are read. Candidates are multiples of
    `multiple_of` (default: the mesh's data-axis extent, so every probe
    shards evenly). `capacity` defaults to mx.memsafe.capacity_bytes()
    (the `device_bytes_limit` knob, else device memory_stats). When
    `buckets` (sequence lengths) is given, the batch search runs at the
    LARGEST bucket and each bucket is then verified at the chosen batch —
    the result's `.bucket_pad()` keeps exactly the fitting boundaries.

    Returns an AutofitResult; raises MemoryBudgetError when even the
    smallest candidate does not fit (carrying that candidate's plan)."""
    from . import memsafe as _memsafe

    cap = capacity if capacity is not None else _memsafe.capacity_bytes()
    if not cap:
        raise ValueError(
            "autofit needs a device capacity: set the device_bytes_limit "
            "knob (simulated capacity), pass capacity=, or run on a "
            "backend whose device.memory_stats() reports bytes_limit")
    cap = int(cap)
    m = int(multiple_of) if multiple_of else _data_axis_extent(trainer)
    k_max = max(1, int(max_batch) // m)
    probes = []

    def plan(batch_size, seq_len=None):
        batch = make_batch(batch_size) if seq_len is None \
            else make_batch(batch_size, seq_len)
        data, labels = batch
        info = trainer.predict_step_bytes(data, labels)
        # capacity/headroom/fits re-derived against THE SEARCH capacity
        # (the caller's capacity= may differ from the memsafe-global one
        # predict_step_bytes consulted) so every probe record is
        # internally consistent
        info = dict(info, batch_size=batch_size, seq_len=seq_len,
                    capacity_bytes=cap,
                    headroom_bytes=cap - info["predicted_bytes"],
                    fits=info["predicted_bytes"] <= cap)
        probes.append(info)
        if verbose:
            print(f"mx.dataflow.autofit: batch {batch_size}"
                  + (f" seq {seq_len}" if seq_len is not None else "")
                  + f" -> predicted {info['predicted_bytes']} bytes "
                  f"({'fits' if info['fits'] else 'over'} capacity {cap})",
                  file=sys.stderr)
        return info

    # anchor the batch search at the LARGEST bucket that fits at the
    # minimum batch; buckets too big for even that are dropped (logged),
    # not fatal — only when NOTHING fits does autofit raise
    dropped = []
    top_seq = None
    lo_info = None
    for cand in (sorted((int(b) for b in buckets), reverse=True)
                 if buckets else [None]):
        lo_info = plan(m, cand)
        if lo_info["fits"]:
            top_seq = cand
            break
        dropped.append(cand)
    if lo_info is None or not lo_info["fits"]:
        raise _memsafe.MemoryBudgetError(
            f"autofit(batch={m})", lo_info["predicted_bytes"], cap,
            exec_peak_bytes=lo_info["exec_peak_bytes"],
            resident_bytes=lo_info["resident_bytes"])
    if dropped and verbose:
        print(f"mx.dataflow.autofit: bucket(s) {sorted(dropped)} exceed "
              f"capacity even at batch {m} — dropped", file=sys.stderr)
    # largest fitting k in [1, k_max]: invariant fits(lo), not fits(hi)
    lo, hi = 1, None
    best = lo_info
    next_larger = None
    if k_max > 1:
        hi_info = plan(k_max * m, top_seq)
        if hi_info["fits"]:
            lo, best = k_max, hi_info
        else:
            hi = k_max
            next_larger = hi_info
            while hi - lo > 1:
                mid = (lo + hi) // 2
                info = plan(mid * m, top_seq)
                if info["fits"]:
                    lo, best = mid, info
                else:
                    hi, next_larger = mid, info
    chosen = lo * m
    fitting_buckets = None
    if buckets:
        fitting_buckets = []
        for L in sorted(int(b) for b in buckets):
            if L in dropped:
                continue
            if L == top_seq:
                # already planned: the batch search ran at this bucket
                fitting_buckets.append(L)
                continue
            if plan(chosen, L)["fits"]:
                fitting_buckets.append(L)
    nl = None
    if next_larger is not None:
        nl = {"batch_size": next_larger["batch_size"],
              "predicted_bytes": next_larger["predicted_bytes"]}
    result = AutofitResult(chosen, best, cap, probes,
                           buckets=fitting_buckets, next_larger=nl)
    if verbose:
        print(f"mx.dataflow.autofit: chose batch {chosen} "
              f"(predicted {result.predicted_bytes} of {cap} bytes, "
              f"headroom {result.headroom_bytes})"
              + (f", buckets {fitting_buckets}" if buckets else "")
              + (f"; batch {nl['batch_size']} would NOT fit "
                 f"({nl['predicted_bytes']} bytes)" if nl else
                 "; search capped at max_batch"),
              file=sys.stderr)
    return result


def _data_axis_extent(trainer):
    """Devices the batch axis shards over (dp*fsdp), so autofit probes
    only evenly-sharding batch sizes; 1 when the trainer has no mesh."""
    mesh = getattr(trainer, "mesh", None)
    if mesh is None:
        return 1
    try:
        return int(mesh.shape.get("dp", 1)) * int(mesh.shape.get("fsdp", 1))
    except Exception:
        return 1


# ---------------------------------------------------------------------------
# persistent XLA compilation cache
# ---------------------------------------------------------------------------

_cache_dir = None                 # wired cache directory, once decided
_cache_lock = _locklint.make_lock("dataflow.compile_cache")


def ensure_compile_cache():
    """Turn on jax's persistent compilation cache and return its
    directory (idempotent; called at first trainer construction and by
    the entry-point scripts). THE one place that decides where the cache
    lives: where `JAX_COMPILATION_CACHE_DIR` is set, jax has already
    read it and nothing here moves it; otherwise the cache goes to the
    fixed `<checkout>/.jax_cache` — never a temporary or per-process
    path, because the path is part of what makes the next run hit.
    Relaunches then deserialize executables instead of recompiling; hits
    and misses land in the compile_cache_hits_total /
    compile_cache_misses_total telemetry counters."""
    global _cache_dir
    with _cache_lock:
        if _cache_dir is not None:
            return _cache_dir
        import jax
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache_dir:
            cache_dir = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
        _register_cache_listener()
        _cache_dir = cache_dir
        return cache_dir


def _register_cache_listener():
    """Mirror jax's compilation-cache hit/miss monitoring events into the
    telemetry counters, so reports can separate warm (deserialized) from
    cold (full XLA) compiles."""
    from jax import monitoring

    def _on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            _M_CACHE_HITS.inc()
        elif event == "/jax/compilation_cache/cache_misses":
            _M_CACHE_MISSES.inc()

    monitoring.register_event_listener(_on_event)
