"""mx.resilience — preemption-safe training: atomic verified checkpoints,
auto-resume, graceful SIGTERM handling, transient-fault retry, and a
fault-injection harness.

TPU pods are preemptible and multi-host: a production framework must
survive rank death, SIGTERM preemption, and torn/corrupt checkpoints.
The reference's KVStore/PS-Lite lineage treated worker failure as a
first-class event; this module is the TPU-native equivalent. Five pieces:

  * **atomic verified checkpoints** — every managed checkpoint is written
    to a temp directory, described by a `manifest.json` carrying per-file
    CRC32 checksums + the step id + a mesh/config fingerprint, fsynced,
    and atomically renamed into place. A kill mid-save leaves only a
    `*.tmp-*` directory that restore never considers. On restore the
    checksums are verified, a mesh/param-mode change is REDISTRIBUTED
    onto the current topology (parallel/reshard.py; bit-exact, planned
    from the manifest's recorded per-array shardings) while the
    `reshard` knob allows it — or rejected with `MeshMismatchError`
    when reshard='off' — and a torn/corrupt latest checkpoint falls
    back to the newest previous GOOD one.
  * **auto-resume** — the `resume` knob ("auto" or an explicit path) makes
    a fresh `ShardedTrainer` (and `Estimator.fit(resume=...)`) restore
    model/optimizer/RNG/device-step-counter from the newest verified
    checkpoint; already-consumed steps/epochs are skipped by the restored
    counters.
  * **graceful preemption** — `install()` registers a SIGTERM/SIGINT
    handler that only sets a flag (async-signal-safe); the trainer
    finishes the in-flight step, writes a final checkpoint, and exits
    with the distinct `EXIT_PREEMPTED` code so supervisors can tell
    "saved and evicted" from "crashed".
  * **RetryPolicy** — exponential backoff + jitter + retryable-exception
    classification, applied to transient faults: prefetch staging in
    `dataflow.prefetch_to_mesh`, silent DataLoader worker death
    (respawn + work re-enqueue), and checkpoint I/O.
  * **fault injection** — the `fault_inject` knob ("sigterm@step:5",
    "kill@step:3@rank:1", "corrupt_ckpt@step:4", "stall_input:250")
    drives deterministic failures through the SAME hooks production uses,
    so every recovery path is provable end-to-end (tests/unittest/
    test_resilience.py; `tools/launch.py --max-restarts` supervises the
    relaunch side).

Cost model: DISABLED (the default) is the production fast path — the
trainer hook is one module-bool check, no signal handlers are installed,
`save_states` writes exactly what it wrote before (no manifest, no
hashing), and restore verifies nothing (`ci/run.sh sanity` asserts
this). Enable with `mx.resilience.install()` / `MXNET_TPU_RESILIENCE=1`.
"""
from __future__ import annotations

import json
import os
import random as _pyrandom
import shutil
import signal as _signal
import sys
import threading
import time
import zlib

from . import _locklint
from . import config as _config
from . import diagnostics as _diagnostics
from . import goodput as _goodput
from . import guard as _guard
from . import telemetry as _telemetry
from . import trace as _trace

__all__ = [
    "enable", "disable", "enabled", "install", "uninstall", "preempted",
    "clear_preempted", "RetryPolicy", "retry_call", "CheckpointCorruptError",
    "MeshMismatchError", "PreemptedExit", "EXIT_PREEMPTED",
    "write_checkpoint", "verify_checkpoint", "list_checkpoints",
    "check_fingerprint", "trainer_fingerprint", "CheckpointManager",
    "manager_for", "FaultInjector", "fault_point", "restart_count",
    "last_resume", "note_preemption", "save_estimator", "restore_estimator",
    "EXIT_SHRINK", "EXIT_GROW", "reshard_gate", "request_shrink",
]

# distinct "preempted: state saved, exiting on request" process exit code —
# chosen outside the shell (126..128+N) and common-errno ranges so a
# supervisor (tools/launch.py, k8s) can classify it unambiguously
EXIT_PREEMPTED = 83
# elastic reshape requests (fault-injectable via shrink@step / grow@step;
# honored by tools/launch.py --elastic): state saved, exiting so the
# supervisor can relaunch the gang one worker smaller / larger
EXIT_SHRINK = 84
EXIT_GROW = 85

_lock = _locklint.make_rlock("resilience.state")
_enabled = False          # the fast-path bool: trainer hooks check ONLY this
_installed = False        # signal handlers chained
_prev_handlers = {}
_preempt = {"flag": False, "signum": None}
_injector = None          # FaultInjector parsed from the fault_inject knob
_resume_info = None       # {"path", "step", "fallbacks"} of the last restore
_pending_reshard = None   # staged by reshard.note_reshard for _note_resume

_M_SAVE_SECONDS = _telemetry.histogram(
    "checkpoint_save_seconds", "wall time of one managed checkpoint save "
    "(state write + manifest hash + atomic rename)")
_M_RESTORE_SECONDS = _telemetry.histogram(
    "checkpoint_restore_seconds", "wall time of one verified checkpoint "
    "restore (checksum verify + state load)")
_M_VERIFY_FAILURES = _telemetry.counter(
    "checkpoint_verify_failures_total", "checkpoints rejected at restore "
    "time (torn write, checksum mismatch, missing manifest entry) — each "
    "one fell back to an older checkpoint")
_M_RESTARTS = _telemetry.counter(
    "restarts_total", "supervised gang relaunches this process has been "
    "through (from MXNET_TPU_RESTART_COUNT, exported by tools/launch.py "
    "--max-restarts)")
_M_PREEMPTIONS = _telemetry.counter(
    "preemptions_total", "SIGTERM/SIGINT preemptions handled gracefully "
    "(final checkpoint written, exited EXIT_PREEMPTED)")
_M_RETRIES = _telemetry.counter(
    "retries_total", "transient-fault retries by site (label site=): "
    "prefetch staging, dataloader worker respawn, checkpoint I/O")


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed verification (torn write / checksum mismatch /
    missing manifest or entry). Managed restores fall back to the newest
    previous good checkpoint instead of propagating this."""


class MeshMismatchError(RuntimeError):
    """A verified checkpoint was written for a different mesh/param-mode
    than the trainer restoring it, and the `reshard` knob is off (or the
    mismatch is not a topology at all — e.g. a different trainer class).
    With reshard='auto' (the default) a pure mesh/param-mode mismatch is
    redistributed via parallel/reshard.py instead of raising. Carries
    `.mismatch` ({key: (checkpoint, current)}) so callers can tell a
    reshardable topology change from a structural one."""

    def __init__(self, message, mismatch=None):
        super().__init__(message)
        self.mismatch = dict(mismatch or {})


class PreemptedExit(SystemExit):
    """SystemExit subclass raised after the final preemption checkpoint;
    carries EXIT_PREEMPTED (or EXIT_SHRINK/EXIT_GROW for injected elastic
    reshape requests) so the process exit code is distinct."""

    def __init__(self, message="", code=EXIT_PREEMPTED):
        super().__init__(code)
        self.message = message


# ---------------------------------------------------------------------------
# enable / install
# ---------------------------------------------------------------------------

def enabled():
    """True when the resilience layer is armed (hot paths read the module
    global `_enabled` directly — this accessor is the public spelling)."""
    return _enabled


def enable():
    """Arm the trainer hooks (periodic checkpoint, fault injection, resume)
    WITHOUT touching signal handlers — install() adds those."""
    global _enabled, _injector
    with _lock:
        _injector = FaultInjector.from_config()
        _enabled = True


def disable():
    global _enabled
    _enabled = False


def install(signals=(_signal.SIGTERM, _signal.SIGINT)):
    """Arm everything: enable() plus a preemption handler on `signals`
    that only sets a flag (async-signal-safe); the in-flight step finishes,
    a final checkpoint is written at the step boundary, and the process
    exits EXIT_PREEMPTED. Also publishes the supervised-relaunch count
    (MXNET_TPU_RESTART_COUNT) into the restarts_total counter and the
    diagnostics ring. Idempotent."""
    global _installed
    enable()
    with _lock:
        if not _installed:
            for sig in signals:
                try:
                    _prev_handlers[sig] = _signal.signal(sig, _on_signal)
                except (ValueError, OSError):
                    pass           # non-main thread / restricted env
            _installed = True
    n = restart_count()
    if n:
        _M_RESTARTS.inc(n)
        _diagnostics.record_event("restart", count=n)
    return _installed


def uninstall():
    """Undo install() (tests): restore previous signal handlers, disarm
    the hooks, drop the preemption flag and per-trainer managers."""
    global _injector, _resume_info, _pending_reshard
    with _lock:
        if _installed:
            _restore_handlers()
        _injector = None
        _resume_info = None
        _pending_reshard = None
        clear_preempted()
    disable()


def _on_signal(signum, frame):
    # First signal: set a flag, nothing else — saving from the signal
    # frame mid-dispatch could serialize half-updated device state; the
    # trainer/fit loop checks the flag at the next step boundary.
    # Second signal: ESCALATE — restore the previous handlers and
    # re-deliver, so a phase with no step boundary in sight (data prep,
    # a minutes-long first compile, a plain user loop with no resilience
    # hook) stays terminable and Ctrl-C twice still kills the process.
    if _preempt["flag"]:
        print("mx.resilience: second signal — restoring default handlers "
              "and terminating without a final checkpoint", file=sys.stderr)
        _restore_handlers()
        os.kill(os.getpid(), signum)
        return
    _preempt["flag"] = True
    _preempt["signum"] = signum
    print(f"mx.resilience: signal {signum} received — finishing the "
          "in-flight step, then checkpointing and exiting "
          f"{EXIT_PREEMPTED} (send again to terminate immediately)",
          file=sys.stderr)


def _restore_handlers():
    global _installed
    for sig, h in list(_prev_handlers.items()):
        try:
            _signal.signal(sig, h if h is not None else _signal.SIG_DFL)
        except (ValueError, OSError):
            pass
    _prev_handlers.clear()
    _installed = False


def preempted():
    """True once a preemption signal arrived (sticky until
    clear_preempted(); the boundary save does not clear it — training
    loops break on it)."""
    return _preempt["flag"]


def clear_preempted():
    _preempt["flag"] = False
    _preempt["signum"] = None
    _preempt.pop("resize", None)


def restart_count():
    """How many supervised relaunches this process has been through
    (exported by tools/launch.py --max-restarts as
    MXNET_TPU_RESTART_COUNT; 0 on the first launch)."""
    try:
        return int(os.environ.get("MXNET_TPU_RESTART_COUNT", "0"))
    except ValueError:
        return 0


def last_resume():
    """{"path", "step", "fallbacks"} of the most recent successful restore
    in this process (None before any). Surfaced as the post-mortem
    "resume" section by mx.diagnostics."""
    return dict(_resume_info) if _resume_info else None


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------

class RetryPolicy:
    """Exponential backoff + full jitter + retryable-exception
    classification.

    `max_attempts` counts TOTAL tries (1 = no retry). A non-retryable
    exception propagates immediately; a retryable one sleeps
    `backoff_s * 2^k` (capped at `max_backoff_s`, jittered by ±`jitter`
    fraction) and tries again. `call(fn, ..., abort=...)` stops early —
    re-raising the last failure — when the abort callable turns true
    (e.g. a prefetcher closing under the worker)."""

    #: transient by default: filesystem/network hiccups and timeouts.
    #: Framework code passes explicit lists where it knows better.
    DEFAULT_RETRYABLE = (OSError, ConnectionError, TimeoutError)

    def __init__(self, max_attempts=None, backoff_s=None, max_backoff_s=None,
                 jitter=0.25, retryable=None, sleep=time.sleep, rng=None):
        self.max_attempts = int(max_attempts if max_attempts is not None
                                else _config.get("retry_max_attempts"))
        self.backoff_s = float(backoff_s if backoff_s is not None
                               else _config.get("retry_backoff_s"))
        self.max_backoff_s = float(max_backoff_s if max_backoff_s is not None
                                   else _config.get("retry_max_backoff_s"))
        self.jitter = float(jitter)
        self.retryable = tuple(retryable) if retryable is not None \
            else self.DEFAULT_RETRYABLE
        self._sleep = sleep
        self._rng = rng or _pyrandom.Random()

    def is_retryable(self, exc):
        return isinstance(exc, self.retryable)

    def delay(self, attempt):
        """Backoff before try `attempt+2` (attempt is the 0-based index of
        the try that just failed)."""
        base = min(self.backoff_s * (2.0 ** attempt), self.max_backoff_s)
        if self.jitter:
            base *= 1.0 + self.jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, base)

    def call(self, fn, *args, site="generic", abort=None, on_retry=None,
             **kwargs):
        """Run fn(*args, **kwargs) under this policy. `on_retry(exc,
        attempt, delay)` observes each retry; `abort()` true stops the
        loop early, re-raising the last exception."""
        attempt = 0
        while True:
            try:
                return fn(*args, **kwargs)
            except BaseException as e:  # noqa: BLE001 — classified below
                if not self.is_retryable(e) or attempt + 1 >= self.max_attempts:
                    raise
                if abort is not None and abort():
                    raise
                delay = self.delay(attempt)
                if _telemetry._enabled:
                    _M_RETRIES.labels(site=site).inc()
                if on_retry is not None:
                    on_retry(e, attempt, delay)
                else:
                    print(f"mx.resilience: retrying {site} after "
                          f"{type(e).__name__}: {e} (attempt "
                          f"{attempt + 2}/{self.max_attempts}, "
                          f"backoff {delay:.2f}s)", file=sys.stderr)
                self._sleep(delay)
                attempt += 1


def retry_call(fn, *args, **kwargs):
    """Module-level convenience: RetryPolicy() from the config knobs."""
    return RetryPolicy().call(fn, *args, **kwargs)


# ---------------------------------------------------------------------------
# atomic verified checkpoints
# ---------------------------------------------------------------------------

_MANIFEST = "manifest.json"
_STEP_PREFIX = "step_"
_TMP_MARK = ".tmp-"


def _file_crc(path, _bufsize=1 << 20):
    """Streaming CRC32 of one file (cheap enough to run over multi-GB
    checkpoints; the point is torn-write detection, not cryptography)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(_bufsize)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _walk_files(root):
    for dirpath, _dirnames, filenames in os.walk(root):
        for name in sorted(filenames):
            full = os.path.join(dirpath, name)
            yield os.path.relpath(full, root), full


def _jax_process_count():
    """jax.process_count() without cold-initializing a backend: a process
    that never imported jax cannot be part of a multi-host world."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 1
    try:
        return int(jax.process_count())
    except Exception:
        return 1


def write_checkpoint(directory, writer, step=0, fingerprint=None,
                     layouts=None):
    """Atomic verified checkpoint write.

    `writer(tmpdir)` produces the payload (orbax state, .params files,
    anything); then a manifest.json with per-file size+CRC32, the step id
    and the caller's fingerprint is written, everything is fsynced, and
    the temp directory is atomically renamed to `directory` (an existing
    checkpoint there is replaced — see _recover_displaced for the
    crash-between-renames window). A crash leaves either the previous
    checkpoint, a recoverable `*.tmp-old` displacement, or an ignorable
    `*.tmp-<pid>` directory — never a half-written checkpoint that
    restore would trust.

    Multi-host (jax.process_count() > 1): the temp-dir rename dance is a
    per-process filesystem operation and cannot wrap a COLLECTIVE orbax
    save, so the writer runs against the final directory directly (orbax
    brings its own multi-host commit semantics) and only process 0 writes
    the manifest afterwards — shared-filesystem assumption, like the
    orbax layout itself."""
    directory = os.path.abspath(str(directory))
    parent = os.path.dirname(directory) or "."
    os.makedirs(parent, exist_ok=True)
    if _jax_process_count() > 1:
        writer(directory)
        if _process_index() == 0:
            _write_manifest(directory, step, fingerprint, layouts)
        fault_point("ckpt", step=step, path=directory)
        return directory
    tmp = directory + _TMP_MARK + str(os.getpid())
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    try:
        writer(tmp)
        _write_manifest(tmp, step, fingerprint, layouts)
        if os.path.exists(directory):
            # replace-in-place: move the old checkpoint aside first (rename
            # over a non-empty directory is not atomic/portable), remove it
            # only after the new one is in place. A crash between the two
            # renames leaves the good copy at <dir>.tmp-old, which
            # _recover_displaced renames back on the next restore/GC.
            old = directory + _TMP_MARK + "old"
            if os.path.exists(old):
                shutil.rmtree(old)
            os.rename(directory, old)
            os.rename(tmp, directory)
            shutil.rmtree(old, ignore_errors=True)
        else:
            os.rename(tmp, directory)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    _dir_fsync(parent)
    fault_point("ckpt", step=step, path=directory)
    return directory


def _write_manifest(directory, step, fingerprint, layouts=None):
    manifest = {
        "schema": 2,
        "step": int(step),
        "ts": time.time(),
        "fingerprint": fingerprint or {},
        "files": {},
    }
    if layouts:
        # per-array shard layouts (parallel/reshard.state_layouts): lets a
        # restore on a DIFFERENT topology plan the redistribution from
        # metadata alone, before touching any payload
        manifest["shardings"] = list(layouts)
    for rel, full in _walk_files(directory):
        if rel == _MANIFEST:
            continue
        manifest["files"][rel] = {"size": os.path.getsize(full),
                                  "crc32": _file_crc(full)}
    mpath = os.path.join(directory, _MANIFEST)
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())


def _recover_displaced(base_dir):
    """Undo a crash caught between write_checkpoint's two renames: a
    `step_X.tmp-old` directory whose `step_X` is missing IS the last good
    checkpoint — rename it back before anyone lists or GCs."""
    try:
        entries = os.listdir(str(base_dir))
    except (FileNotFoundError, NotADirectoryError):
        return
    suffix = _TMP_MARK + "old"
    for name in entries:
        if not (name.startswith(_STEP_PREFIX) and name.endswith(suffix)):
            continue
        final = os.path.join(str(base_dir), name[:-len(suffix)])
        if not os.path.exists(final):
            try:
                os.rename(os.path.join(str(base_dir), name), final)
                print(f"mx.resilience: recovered displaced checkpoint "
                      f"{final} (crash during a same-step rewrite)",
                      file=sys.stderr)
            except OSError:
                pass


def _dir_fsync(path):
    """fsync a directory so the rename itself is durable (best-effort:
    not all filesystems/platforms allow O_RDONLY dir fds + fsync)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def verify_checkpoint(directory):
    """Verify a managed checkpoint: manifest present, every entry present
    with matching size and CRC32. Returns the manifest dict; raises
    CheckpointCorruptError naming the first bad file."""
    directory = str(directory)
    mpath = os.path.join(directory, _MANIFEST)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        raise CheckpointCorruptError(
            f"{directory}: no {_MANIFEST} — torn write or not a managed "
            "checkpoint") from None
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"{directory}: unreadable {_MANIFEST}: {e}") from None
    for rel, info in manifest.get("files", {}).items():
        full = os.path.join(directory, rel)
        if not os.path.exists(full):
            raise CheckpointCorruptError(f"{directory}: missing file {rel}")
        size = os.path.getsize(full)
        if size != info.get("size"):
            raise CheckpointCorruptError(
                f"{directory}: {rel} is {size} bytes, manifest says "
                f"{info.get('size')}")
        crc = _file_crc(full)
        if crc != info.get("crc32"):
            raise CheckpointCorruptError(
                f"{directory}: {rel} checksum {crc:#010x} != manifest "
                f"{info.get('crc32', 0):#010x} (corrupt)")
    return manifest


#: fingerprint keys a planned redistribution can bridge — anything else
#: differing (e.g. the trainer class) is structural, not topological.
#: "zero" (mx.zero optimizer-state sharding on/off) is a pure layout
#: change: a zero'd checkpoint restores onto an unsharded trainer and
#: vice versa, bit-exactly, via the same planned-reshard path
RESHARDABLE_KEYS = frozenset({"mesh_shape", "param_mode", "zero"})


def check_fingerprint(manifest, expected, directory=""):
    """Reject a checkpoint written for a different mesh/config. Compares
    only the keys `expected` carries, so new fingerprint fields stay
    backward-compatible. The raised MeshMismatchError names BOTH
    fingerprints and the reshard='auto' remediation; callers that may
    redistribute go through reshard_gate() instead."""
    got = manifest.get("fingerprint") or {}
    bad = {k: (got.get(k), v) for k, v in (expected or {}).items()
           if k in got and got[k] != v}
    if bad:
        detail = ", ".join(f"{k}: checkpoint={g!r} current={c!r}"
                           for k, (g, c) in sorted(bad.items()))
        raise MeshMismatchError(
            f"checkpoint {directory or '<dir>'} was written for a different "
            f"topology ({detail}; checkpoint fingerprint {got!r}, current "
            f"{expected!r}). Pass reshard='auto' to load_states / set the "
            "reshard knob (MXNET_TPU_RESHARD=auto) to redistribute it onto "
            "the current mesh, or restore on the original topology.",
            mismatch=bad)


def reshard_gate(manifest, trainer, directory="", reshard=None):
    """check_fingerprint with redistribution awareness: returns False when
    the checkpoint matches the trainer's topology, True when it differs
    ONLY in mesh/param-mode and the reshard policy ('auto'/'host', from
    the argument or the `reshard` knob) allows redistribution. Raises
    MeshMismatchError when resharding is explicitly off, and for
    structural mismatches (different trainer class) regardless of
    policy — no redistribution can bridge those."""
    mode = reshard if reshard not in (None, "") else _config.get("reshard")
    if mode not in ("auto", "off", "host"):
        # an unvalidated per-call override must not fail open: a typo like
        # 'none' silently behaving as 'auto' would reshard exactly where
        # the caller asked for the strict check
        raise ValueError(
            f"reshard={mode!r}: expected 'auto', 'off', or 'host'")
    try:
        check_fingerprint(manifest, trainer_fingerprint(trainer), directory)
    except MeshMismatchError as e:
        if mode == "off" or not e.mismatch \
                or set(e.mismatch) - RESHARDABLE_KEYS:
            raise
        return True
    return False


def list_checkpoints(base_dir):
    """Step-numbered managed checkpoints under base_dir, oldest first:
    [(step, path)]. `*.tmp-*` leftovers from killed saves are excluded."""
    out = []
    try:
        entries = os.listdir(str(base_dir))
    except (FileNotFoundError, NotADirectoryError):
        return out
    for name in entries:
        if not name.startswith(_STEP_PREFIX) or _TMP_MARK in name:
            continue
        try:
            step = int(name[len(_STEP_PREFIX):])
        except ValueError:
            continue
        out.append((step, os.path.join(str(base_dir), name)))
    return sorted(out)


class CheckpointManager:
    """Keep-last-N atomic verified checkpoints of one trainer under
    `base_dir/step_<n>`.

    `trainer` is anything exposing save_states/load_states/num_update
    (ShardedTrainer, the pipeline trainers). Saves go through
    write_checkpoint (manifest + atomic rename) under the checkpoint-I/O
    RetryPolicy; restore_latest walks newest→oldest, verifying checksums
    and the mesh fingerprint, falling back past corrupt checkpoints and
    GCing beyond `keep` after each save."""

    def __init__(self, trainer, base_dir, keep=None, policy=None):
        self.trainer = trainer
        self.base_dir = os.path.abspath(str(base_dir))
        self.keep = int(keep if keep is not None
                        else _config.get("checkpoint_keep"))
        self.policy = policy or RetryPolicy()
        self._last_saved_step = None

    # ------------------------------------------------------------- save
    def _step_dir(self, step):
        return os.path.join(self.base_dir, f"{_STEP_PREFIX}{step:010d}")

    def save(self, force=False):
        """Checkpoint the trainer's current step. Skips (returns None) if
        that step is already saved, unless `force`. The write itself is
        atomic+verified: while resilience is enabled, the trainer's
        save_states routes through write_checkpoint (see
        parallel/trainer._ckpt_save)."""
        step = int(self.trainer.num_update)
        if not force and self._last_saved_step == step:
            return None
        t0 = time.perf_counter()
        path = self._step_dir(step)
        if _guard._enabled:
            # liveness: the supervisor's staleness clock must see the
            # save START (a long write is progress, not a hang)
            _guard.heartbeat(step, phase="checkpoint.save", force=True)
        # a multi-GB (or resharding) checkpoint write is a legitimate
        # long non-step region: suspend the hang watchdog and the
        # mx.guard collective deadline for its duration so neither can
        # falsely fire mid-save (a REAL hang inside still gets named —
        # the suspend context doubles as a diagnostics scope)
        with _diagnostics.suspend_watchdog("checkpoint.save", step):
            self.policy.call(self.trainer.save_states, path,
                             site="checkpoint-io")
        if _guard._enabled:
            _guard.heartbeat(step, phase="checkpoint.save", force=True)
        self._last_saved_step = step
        dt = time.perf_counter() - t0
        if _telemetry._enabled:
            _M_SAVE_SECONDS.observe(dt)
            _telemetry.event("checkpoint", step=step, path=path,
                             dur_s=round(dt, 6))
        if _trace.live():
            # checkpoint saves serialize with the step loop on this rank:
            # a gang whose straggler's timeline shows checkpoint.save where
            # the peers show step spans is checkpoint-bound, not slow
            _trace.record_span("checkpoint.save", t0, t0 + dt, step=step,
                               cat="checkpoint", always=True)
        if _goodput._enabled:
            _goodput.note("checkpoint_save", t0, t0 + dt, step=step)
        _diagnostics.record_event("checkpoint", step=step, path=path,
                                  dur_s=round(dt, 6))
        self._gc()
        return path

    def _gc(self):
        """Retention on process 0: newest `keep` complete checkpoints
        survive; older ones and stale tmp leftovers (killed mid-save,
        older than 5 minutes) go. Displaced `*.tmp-old` checkpoints are
        recovered first so the cleanup can never eat the last good copy."""
        if self.keep <= 0 or not _owns_gc():
            return
        _recover_displaced(self.base_dir)
        for _step, path in list_checkpoints(self.base_dir)[:-self.keep]:
            shutil.rmtree(path, ignore_errors=True)
        try:
            for name in os.listdir(self.base_dir):
                full = os.path.join(self.base_dir, name)
                if _TMP_MARK in name and \
                        time.time() - os.path.getmtime(full) > 300:
                    shutil.rmtree(full, ignore_errors=True)
        except OSError:
            pass

    # ---------------------------------------------------------- restore
    def restore_latest(self, max_step=None):
        """Restore the newest checkpoint that verifies, falling back past
        torn/corrupt ones (each rejection counts
        checkpoint_verify_failures_total). Returns the restored step, or
        None when no usable checkpoint exists. `max_step` bounds the
        search: checkpoints above it are skipped without being counted as
        corrupt (mx.guard's SDC rollback passes the last digest-verified
        step — a CRC-clean file saved from already-corrupt params must
        not be reloaded). A mesh-mismatch raises MeshMismatchError —
        that is a configuration error, not corruption, and older
        checkpoints would mismatch identically."""
        _recover_displaced(self.base_dir)
        ckpts = list_checkpoints(self.base_dir)
        fallbacks = 0
        for step, path in reversed(ckpts):
            if max_step is not None and step > max_step:
                continue
            try:
                self.restore(path)
            except CheckpointCorruptError as e:
                fallbacks += 1
                if _telemetry._enabled:
                    _M_VERIFY_FAILURES.inc()
                print(f"mx.resilience: rejecting checkpoint: {e} — "
                      "falling back to the previous one", file=sys.stderr)
                continue
            _note_resume(path, step, fallbacks)
            return step
        return None

    def restore(self, path):
        """Verify + load one specific checkpoint directory. The checksum
        and fingerprint verification happen INSIDE load_states (the
        trainer's _ckpt_restore verifies whenever resilience is enabled
        and a manifest exists) — running them here too would CRC every
        payload file twice on exactly the relaunch path where recovery
        speed matters; this only insists a manifest is present so an
        unmanaged directory can't slip through unverified."""
        global _pending_reshard
        t0 = time.perf_counter()
        # drop any transition staged by an earlier, unrelated load_states
        # call: only a reshard that happens DURING this restore may attach
        # to the resume record _note_resume writes afterwards
        _pending_reshard = None
        if not os.path.exists(os.path.join(str(path), _MANIFEST)):
            raise CheckpointCorruptError(
                f"{path}: no {_MANIFEST} — torn write or not a managed "
                "checkpoint")
        if not _enabled:
            # load_states only self-verifies while resilience is enabled;
            # a manager used standalone still gets the full check here
            # (reshard_gate: a pure topology change passes through while
            # the reshard knob allows redistribution)
            manifest = verify_checkpoint(path)
            reshard_gate(manifest, self.trainer, str(path))
        # restores (possibly resharding onto a new topology) are long
        # non-step regions too: same watchdog/deadline suspension as save
        with _diagnostics.suspend_watchdog("checkpoint.restore"):
            self.policy.call(self.trainer.load_states, path,
                             site="checkpoint-io")
        if _guard._enabled:
            _guard.heartbeat(int(self.trainer.num_update),
                             phase="checkpoint.restore", force=True)
        self._last_saved_step = int(self.trainer.num_update)
        if _telemetry._enabled:
            _M_RESTORE_SECONDS.observe(time.perf_counter() - t0)
        if _goodput._enabled:
            _goodput.note("checkpoint_restore", t0, time.perf_counter(),
                          step=int(self.trainer.num_update))
        return path

    def last_saved_path(self):
        """Path of this manager's most recent save (None before any)."""
        if self._last_saved_step is None:
            return None
        return self._step_dir(self._last_saved_step)


def trainer_fingerprint(trainer):
    """The topology identity a trainer checkpoint is only valid on:
    trainer class, mesh axis sizes, param mode. Written into the manifest
    at save; compared (key-wise) at verified restore."""
    fp = {"trainer": type(trainer).__name__}
    mesh = getattr(trainer, "mesh", None)
    if mesh is not None:
        try:
            fp["mesh_shape"] = {str(k): int(v)
                                for k, v in dict(mesh.shape).items()}
        except Exception:
            pass
    mode = getattr(trainer, "param_mode", None)
    if mode is not None:
        fp["param_mode"] = mode
    if hasattr(trainer, "_zero"):
        # mx.zero layout identity: restores across the zero'd/unsharded
        # boundary are planned redistributions, not mismatches
        fp["zero"] = bool(trainer._zero)
    return fp


def _process_index():
    """Process index without cold-initializing a backend: env first
    (tools/launch.py exports JAX_PROCESS_ID), then jax.process_index()
    if jax is already imported — the same detection order and jax
    fallback as _jax_process_count, so the multi-host checkpoint path
    can never see count>1 while every host thinks it is index 0."""
    for var in ("JAX_PROCESS_ID", "DMLC_WORKER_ID"):
        v = os.environ.get(var)
        if v is not None:
            try:
                return int(v)
            except ValueError:
                pass
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            pass
    return 0


def _owns_gc():
    """True when this process may delete checkpoints: in a multi-host
    jax world only process 0 (the directory is shared), but a process
    that is its own single-process world owns its checkpoint_dir
    outright — per-rank directories (env rank set, no jax.distributed)
    must still get retention."""
    return _jax_process_count() == 1 or _process_index() == 0


def _note_resume(path, step, fallbacks=0):
    global _resume_info
    _resume_info = {"path": path, "step": int(step),
                    "fallbacks": int(fallbacks)}
    # topology transition, when this resume redistributed across meshes
    # (_pending_reshard staged by reshard.note_reshard during the restore
    # that just finished): the post-mortem resume section then names the
    # reshape. Consumed here so a later same-topology resume can't
    # inherit a stale transition.
    global _pending_reshard
    if _pending_reshard is not None:
        _resume_info["reshard"] = _pending_reshard
        _pending_reshard = None
    print(f"mx.resilience: resumed from {path} (step {step}"
          + (f", {fallbacks} corrupt checkpoint(s) skipped" if fallbacks
             else "") + ")", file=sys.stderr)
    if _telemetry._enabled:
        _telemetry.event("resume", path=path, step=int(step),
                         fallbacks=fallbacks)
    _diagnostics.record_event("resume", path=path, step=int(step),
                              fallbacks=fallbacks)
    if _goodput._enabled:
        # marker for the offline report: replayed-step count must equal
        # the high-water mark minus this restored step
        _goodput.note_resume(int(step))


# ---------------------------------------------------------------------------
# trainer hooks (ShardedTrainer / pipeline trainers call these; both are
# gated on the module bool so the disabled path is one check)
# ---------------------------------------------------------------------------

def manager_for(trainer, base_dir=None):
    """Get-or-create the CheckpointManager for a trainer (None when no
    checkpoint directory is configured). Cached ON the trainer object so
    the manager's lifetime is exactly the trainer's — a module-level map
    would pin every trainer (params, optimizer state and all) for the
    life of the process."""
    base_dir = base_dir or _config.get("checkpoint_dir")
    if not base_dir:
        return None
    mgr = getattr(trainer, "_resilience_mgr", None)
    if mgr is None or os.path.abspath(str(base_dir)) != mgr.base_dir:
        mgr = CheckpointManager(trainer, base_dir)
        trainer._resilience_mgr = mgr
    return mgr


def on_trainer_init(trainer):
    """Called at ShardedTrainer construction while enabled: auto-resume
    per the `resume` knob ("auto" = newest verified checkpoint under
    checkpoint_dir; an explicit path = that checkpoint, verified)."""
    resume = _config.get("resume")
    if not resume:
        return None
    if not getattr(trainer, "_ready", True):
        print("mx.resilience: trainer has deferred-shape parameters — "
              "auto-resume skipped (run one step, then load_states "
              "explicitly)", file=sys.stderr)
        return None
    if resume == "auto":
        mgr = manager_for(trainer)
        if mgr is None:
            return None
        return mgr.restore_latest()
    mgr = CheckpointManager(trainer, os.path.dirname(
        os.path.abspath(resume)) or ".")
    mgr.restore(resume)
    _note_resume(resume, int(trainer.num_update))
    return int(trainer.num_update)


def on_step(trainer):
    """The per-step resilience hook (called only while enabled): periodic
    checkpoint FIRST (so a same-step fault resumes past itself), then
    fault injection, then the preemption flag — finishing the in-flight
    step, writing a final checkpoint, and exiting EXIT_PREEMPTED."""
    step = int(trainer.num_update)
    mgr = manager_for(trainer)
    every = _config.get("checkpoint_every_n_steps")
    if mgr is not None and every > 0 and step % every == 0:
        mgr.save()
    if _injector is not None:
        _injector.fire("step", step=step, trainer=trainer)
    if _preempt["flag"]:
        _finalize_preemption(mgr, step)


def request_shrink(reason=None):
    """Ask this rank out of the gang at the NEXT step boundary:
    piggybacks on the preemption machinery — on_step's flag check saves
    a final checkpoint and raises PreemptedExit(EXIT_SHRINK), so a
    tools/launch.py --elastic supervisor relaunches the gang one worker
    smaller without this rank. How mx.guard quarantines a repeat-SDC
    rank (hardware corrupting data faster than rollback launders it)."""
    print(f"mx.resilience: shrink requested"
          + (f" ({reason})" if reason else "")
          + " — exiting EXIT_SHRINK at the next step boundary",
          file=sys.stderr)
    _preempt["flag"] = True
    _preempt["resize"] = "shrink"


def note_preemption(step, path=None, signum=None, kind=None):
    """Record one graceful preemption in telemetry + diagnostics (shared
    by the trainer and estimator preemption paths, so preemptions_total
    means the same thing whichever loop handled the signal). `kind` marks
    injected elastic reshape requests ("shrink"/"grow") apart from real
    preemptions."""
    signum = signum if signum is not None else _preempt["signum"]
    if _telemetry._enabled:
        _M_PREEMPTIONS.inc()
        _telemetry.event("preempt", step=step, signum=signum, path=path,
                         request=kind or "preempt")
    _diagnostics.record_event("preempt", step=step, signum=signum,
                              path=path, request=kind or "preempt")


def _finalize_preemption(mgr, step):
    signum = _preempt["signum"]
    resize = _preempt.get("resize")
    path = None
    save_failed = False
    if mgr is not None:
        try:
            # save() dedupes a step the periodic hook just wrote — that
            # existing checkpoint is still THE final state, so report it
            path = mgr.save() or mgr.last_saved_path()
        except Exception as e:         # noqa: BLE001 — still exit, loudly
            save_failed = True
            print(f"mx.resilience: final preemption checkpoint failed: {e}",
                  file=sys.stderr)
    note_preemption(step, path=path, signum=signum, kind=resize)
    if save_failed:
        # EXIT_PREEMPTED means "state saved, safe to resume the last
        # interval" — a failed final save must NOT claim it. Exit with
        # the conventional fatal-signal code so supervisors see the loss.
        code = 128 + int(signum or _signal.SIGTERM)
        print(f"mx.resilience: preempted (signal {signum}) but the final "
              f"checkpoint FAILED — exiting {code}, resume will use the "
              "last periodic checkpoint", file=sys.stderr)
        raise SystemExit(code)
    code = {"shrink": EXIT_SHRINK, "grow": EXIT_GROW}.get(resize,
                                                          EXIT_PREEMPTED)
    what = f"{resize} requested" if resize else f"preempted (signal {signum})"
    msg = (f"mx.resilience: {what} — "
           + (f"checkpoint saved at step {step} ({path}); " if path
              else "no checkpoint_dir configured; ")
           + f"exiting {code}"
           + (" (an elastic supervisor reshapes the gang)" if resize else ""))
    print(msg, file=sys.stderr)
    raise PreemptedExit(msg, code=code)


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

class FaultInjector:
    """Deterministic fault injection driven by the `fault_inject` knob.

    Spec grammar (comma-separated list):
      sigterm@step:5        — raise SIGTERM in-process after step 5 completes
      kill@step:3           — SIGKILL the process after step 3 (rank death)
      corrupt_ckpt@step:4   — flip bytes in the checkpoint written at step 4
                              (AFTER its manifest: restore must detect it)
      stall_input:250       — one 250 ms stall inside the input pipeline
      exc@step:2            — raise RuntimeError after step 2 (crash path)
      oom@step:3            — raise a synthetic RESOURCE_EXHAUSTED at the
                              DISPATCH of step 3 (before any transfer or
                              donation, like a pre-flight rejection), so
                              every rung of the mx.memsafe oom_recover
                              degradation ladder is drivable in tests;
                              repeat the spec to OOM the retry too and
                              walk further rungs
      shrink@step:3         — after step 3: save a final checkpoint and exit
                              EXIT_SHRINK (84) — an elastic supervisor
                              relaunches the gang SMALLER by every rank
                              that fired (append @rank:N to lose exactly
                              one worker; untargeted, the whole gang
                              shrinks to the --min-workers floor); the
                              resumed workers reshard the checkpoint onto
                              the surviving topology
      grow@step:3           — same, exit EXIT_GROW (85): relaunch one
                              worker LARGER (capacity returned), capped at
                              the original -n
      hang@step:3           — the step-3 boundary BLOCKS and never
                              returns: a stuck collective / wedged host.
                              The heartbeat goes stale, the tools/
                              launch.py --heartbeat-timeout poll kills
                              the stuck-but-alive process (slot loss →
                              elastic relaunch), and any peer stuck
                              waiting trips its mx.guard collective
                              deadline
      corrupt_grad@step:4   — deterministic bit-flip in ONE REPLICA of
                              the first gradient/parameter leaf as the
                              step-4 update lands — the silent data
                              corruption the mx.guard digest vote must
                              catch, attribute by majority, and roll
                              back past
      stall_heartbeat:500   — suppress heartbeat FILE writes for 500 ms
                              (consumed by mx.guard at its next beat):
                              the process stays healthy, only its
                              liveness signal goes dark — the
                              supervisor-side staleness drill
      slow_client:200       — mx.serve: the request STREAM consumer
                              stalls 200 ms per token (consumed by
                              Request.stream at its first read); the
                              scheduler's throughput must not care
      burst:8@step:3        — mx.serve: at scheduler step 3 the server
                              fires its on_burst hook with 8 — a
                              deterministic load spike driving the
                              shed / backpressure paths
      cancel@req:2          — mx.serve: cancel request id 2 at the next
                              scheduler step (append @step:N to pick
                              the step) — the mid-generation
                              cancellation drill; the slot is evicted
                              between decode steps
      kill_replica@step:3   — mx.fleet: SIGKILL this serving replica at
                              scheduler step 3, mid-generation — the
                              router must fail its in-flight requests
                              over to survivors (bit-identical replay
                              past the streamed high-water) and the
                              supervisor must relaunch the worker
      wedge_replica@step:3  — mx.fleet: park the serving scheduler
                              forever at step 3 WITHOUT dying — health
                              checks keep answering while tokens stop;
                              the router's per-read stall bound
                              (fleet_stall_timeout_ms) must fail over
      slow_replica:200      — mx.fleet: this replica's endpoint delays
                              every streamed token 200 ms (consumed by
                              the ReplicaEndpoint at its first submit)
                              — published TTFT degrades and placement
                              must shift load to faster replicas
    Any spec may append @rank:N to fire on that rank only. Specs fire at
    most once, and only on the FIRST launch (MXNET_TPU_RESTART_COUNT=0)
    unless @every_restart is appended — a relaunched gang must not re-kill
    itself at the same step forever."""

    def __init__(self, specs):
        self._specs = list(specs)

    @classmethod
    def from_config(cls):
        raw = _config.get("fault_inject")
        if not raw:
            return None
        return cls.parse(raw)

    @classmethod
    def parse(cls, raw):
        specs = []
        for part in str(raw).split(","):
            part = part.strip()
            if not part:
                continue
            fields = part.split("@")
            head = fields[0]
            kind, _, arg = head.partition(":")
            spec = {"kind": kind, "arg": arg, "step": None, "rank": None,
                    "req": None, "every_restart": False, "fired": False}
            for field in fields[1:]:
                k, _, v = field.partition(":")
                if k == "step":
                    spec["step"] = int(v)
                elif k == "rank":
                    spec["rank"] = int(v)
                elif k == "req":
                    spec["req"] = int(v)
                elif k == "every_restart":
                    spec["every_restart"] = True
                else:
                    raise ValueError(
                        f"fault_inject: unknown qualifier {field!r} in "
                        f"{part!r}")
            if spec["kind"] not in ("sigterm", "kill", "corrupt_ckpt",
                                    "stall_input", "exc", "shrink", "grow",
                                    "oom", "hang", "corrupt_grad",
                                    "stall_heartbeat", "slow_client",
                                    "burst", "cancel", "kill_replica",
                                    "wedge_replica", "slow_replica"):
                raise ValueError(
                    f"fault_inject: unknown fault {spec['kind']!r} in "
                    f"{part!r} (know: sigterm, kill, corrupt_ckpt, "
                    "stall_input, exc, shrink, grow, oom, hang, "
                    "corrupt_grad, stall_heartbeat, slow_client, burst, "
                    "cancel, kill_replica, wedge_replica, slow_replica)")
            specs.append(spec)
        return cls(specs)

    def fire(self, point, step=None, path=None, trainer=None):
        """Run every armed spec matching this fault point. `point` is
        "step" (trainer step boundary), "dispatch" (about to dispatch a
        step; nothing transferred or donated yet), "ckpt" (checkpoint
        just written), or "input" (input pipeline worker). `trainer` is
        handed through at the step boundary so corrupt_grad can reach
        the live parameter replicas."""
        rank = _process_index()
        for spec in self._specs:
            if spec["fired"]:
                continue
            if spec["rank"] is not None and spec["rank"] != rank:
                continue
            if not spec["every_restart"] and restart_count() > 0:
                continue
            kind = spec["kind"]
            if point == "step" and kind in ("sigterm", "kill", "exc",
                                            "hang"):
                if spec["step"] is not None and step != spec["step"]:
                    continue
                spec["fired"] = True
                self._fire_process_fault(kind, step)
            elif point == "step" and kind == "corrupt_grad":
                if spec["step"] is not None and step != spec["step"]:
                    continue
                spec["fired"] = True
                self.corrupt_gradient(trainer, step)
            elif point == "step" and kind in ("shrink", "grow"):
                if spec["step"] is not None and step != spec["step"]:
                    continue
                spec["fired"] = True
                # elastic reshape request: piggyback on the preemption
                # machinery — on_step's flag check (which runs AFTER this
                # fire, in the same step boundary) saves the final
                # checkpoint and exits EXIT_SHRINK/EXIT_GROW
                print(f"mx.resilience: fault injection: {kind} at step "
                      f"{step} (rank {_process_index()})", file=sys.stderr)
                _preempt["flag"] = True
                _preempt["resize"] = kind
            elif point == "dispatch" and kind == "oom":
                if spec["step"] is not None and step != spec["step"]:
                    continue
                spec["fired"] = True
                print(f"mx.resilience: fault injection: synthetic "
                      f"RESOURCE_EXHAUSTED at dispatch of step {step} "
                      f"(rank {rank})", file=sys.stderr)
                from . import memsafe as _memsafe
                raise _memsafe.SimulatedResourceExhausted(step=step)
            elif point == "ckpt" and kind == "corrupt_ckpt":
                if spec["step"] is not None and step != spec["step"]:
                    continue
                spec["fired"] = True
                self.corrupt_checkpoint(path)
            elif point == "input" and kind == "stall_input":
                spec["fired"] = True
                ms = float(spec["arg"] or 100)
                print(f"mx.resilience: fault injection: stalling input "
                      f"{ms:.0f} ms", file=sys.stderr)
                time.sleep(ms / 1000.0)

    def _fire_process_fault(self, kind, step):
        print(f"mx.resilience: fault injection: {kind} at step {step} "
              f"(rank {_process_index()})", file=sys.stderr)
        sys.stderr.flush()
        if kind == "sigterm":
            os.kill(os.getpid(), _signal.SIGTERM)
        elif kind == "kill":
            os.kill(os.getpid(), _signal.SIGKILL)   # no cleanup: rank death
        elif kind == "exc":
            raise RuntimeError(
                f"mx.resilience fault injection: crash at step {step}")
        elif kind == "hang":
            # stuck collective / wedged host: the step boundary never
            # returns. SIGTERM can't break the loop (the resilience
            # handler is flag-only by design) — exactly the stuck-but-
            # alive process the heartbeat-staleness kill exists for.
            while True:
                time.sleep(3600)

    def take(self, kind, step=None, ready=None):
        """Pop one armed spec of `kind` for a caller that implements the
        fault itself (mx.serve's scheduler: burst, cancel). Honors @rank
        and the one-shot / first-launch-only disarm rules; a spec with
        @step:N fires only when `step` matches, a step-less spec fires
        at the first opportunity. `ready(spec)` False leaves the spec
        ARMED instead of consuming it — how a step-less cancel@req:N
        waits for request N to exist rather than burning itself on an
        idle scheduler tick. Returns {"arg", "req"} or None."""
        rank = _process_index()
        for spec in self._specs:
            if spec["fired"] or spec["kind"] != kind:
                continue
            if spec["rank"] is not None and spec["rank"] != rank:
                continue
            if not spec["every_restart"] and restart_count() > 0:
                continue
            if spec["step"] is not None and step != spec["step"]:
                continue
            if ready is not None and not ready(spec):
                continue
            spec["fired"] = True
            return {"arg": spec["arg"] or "", "req": spec["req"]}
        return None

    def consume(self, kind):
        """Pop one armed spec of `kind` (honoring @rank targeting and
        the one-shot / first-launch-only disarm rules) and return its
        arg string, or None. How point-less specs like stall_heartbeat
        reach the subsystem that implements them (mx.guard,
        mx.serve's slow_client)."""
        rank = _process_index()
        for spec in self._specs:
            if spec["fired"] or spec["kind"] != kind:
                continue
            if spec["rank"] is not None and spec["rank"] != rank:
                continue
            if not spec["every_restart"] and restart_count() > 0:
                continue
            spec["fired"] = True
            return spec["arg"] or ""
        return None

    @staticmethod
    def corrupt_gradient(trainer, step):
        """Deterministic silent data corruption: flip one bit in ONE
        REPLICA (the first addressable device's copy) of the first
        gradient/parameter leaf, as the step's update lands. Flipping a
        single replica — not the logical array — reproduces real SDC
        (one chip computed wrong bytes) and leaves the majority of
        replicas clean, so the mx.guard digest vote can attribute the
        corruption to this rank even in a 2-rank gang (15-vs-1 over an
        8-device mesh pair, not an unresolvable 1-vs-1 tie)."""
        if trainer is None or not hasattr(trainer, "params"):
            return
        import jax
        import numpy as np

        params = trainer.params
        leaf_is_list = isinstance(params, (list, tuple))
        leaf = params[0] if leaf_is_list else params
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            datas = [np.array(s.data) for s in shards]
            buf = datas[0].view(np.uint8).reshape(-1)
            buf[buf.size // 2] ^= 0x10
            arrs = [jax.device_put(d, s.device)
                    for d, s in zip(datas, shards)]
            new = jax.make_array_from_single_device_arrays(
                leaf.shape, leaf.sharding, arrs)
            where = f"replica on device {shards[0].device.id}"
        else:
            data = np.array(leaf)
            buf = data.view(np.uint8).reshape(-1)
            buf[buf.size // 2] ^= 0x10
            new = data
            where = "host copy (no device replicas)"
        if leaf_is_list:
            params[0] = new
        else:
            trainer.params = new
        print(f"mx.resilience: fault injection: corrupt_grad at step "
              f"{step} (rank {_process_index()}): flipped one bit in "
              f"param leaf 0, {where}", file=sys.stderr)

    @staticmethod
    def corrupt_checkpoint(path):
        """Flip bytes in the largest payload file of a written checkpoint
        WITHOUT touching its manifest — exactly the torn-write/bit-rot
        case verify_checkpoint must catch."""
        if not path or not os.path.isdir(path):
            return
        target, size = None, -1
        for rel, full in _walk_files(path):
            if rel == _MANIFEST:
                continue
            s = os.path.getsize(full)
            if s > size:
                target, size = full, s
        if target is None or size == 0:
            return
        with open(target, "r+b") as f:
            f.seek(size // 2)
            chunk = f.read(1)
            f.seek(size // 2)
            f.write(bytes([chunk[0] ^ 0xFF if chunk else 0xFF]))
        print(f"mx.resilience: fault injection: corrupted {target}",
              file=sys.stderr)


def fault_point(point, step=None, path=None, trainer=None):
    """Hook production code paths call (only does anything while enabled
    AND a fault_inject spec is armed — the common case is one None
    check)."""
    inj = _injector
    if inj is not None and _enabled:
        inj.fire(point, step=step, path=path, trainer=trainer)


# ---------------------------------------------------------------------------
# estimator checkpointing (epoch-granularity fit-loop state)
# ---------------------------------------------------------------------------

_FIT_STATE = "fit_state.json"


def save_estimator(est, base_dir):
    """Atomic verified checkpoint of an Estimator fit loop: net params,
    gluon-Trainer optimizer state, epoch/batch counters, global RNG.
    Called at epoch boundaries only — a mid-epoch save would be replayed
    against from the epoch's start and double-apply the partial epoch."""
    import jax
    import numpy as np

    from . import random as _random

    epoch = int(est.num_epoch)

    def _writer(tmp):
        est.net.save_parameters(os.path.join(tmp, "net.params"))
        est.trainer.save_states(os.path.join(tmp, "trainer.states"))
        key = np.asarray(jax.random.key_data(_random.get_state()))
        state = {"num_epoch": epoch, "num_batch": int(est.num_batch),
                 "rng_key": [int(x) for x in key.ravel()],
                 "rng_shape": list(key.shape),
                 "rng_dtype": str(key.dtype)}
        with open(os.path.join(tmp, _FIT_STATE), "w") as f:
            json.dump(state, f)
    t0 = time.perf_counter()
    path = RetryPolicy().call(
        write_checkpoint,
        os.path.join(str(base_dir), f"{_STEP_PREFIX}{epoch:010d}"),
        _writer, step=epoch, fingerprint={"trainer": "Estimator"},
        site="checkpoint-io")
    if _telemetry._enabled:
        _M_SAVE_SECONDS.observe(time.perf_counter() - t0)
        _telemetry.event("checkpoint", step=epoch, path=path,
                         dur_s=round(time.perf_counter() - t0, 6))
    _gc_estimator(base_dir)
    return path


def _gc_estimator(base_dir):
    keep = int(_config.get("checkpoint_keep"))
    if keep <= 0 or not _owns_gc():
        return
    _recover_displaced(base_dir)
    for _step, path in list_checkpoints(base_dir)[:-keep]:
        shutil.rmtree(path, ignore_errors=True)


def restore_estimator(est, base_dir, resume="auto"):
    """Restore the newest verified Estimator checkpoint (or the explicit
    `resume` path), falling back past corrupt ones. Returns the restored
    epoch or None. The fit loop then skips already-consumed epochs via
    the restored num_epoch."""
    import numpy as np

    from . import random as _random

    _recover_displaced(base_dir)
    if resume != "auto":
        candidates = [(None, str(resume))]
    else:
        candidates = list(reversed(list_checkpoints(base_dir)))
    fallbacks = 0
    for _step, path in candidates:
        try:
            manifest = verify_checkpoint(path)
            check_fingerprint(manifest, {"trainer": "Estimator"}, path)
            with open(os.path.join(path, _FIT_STATE)) as f:
                state = json.load(f)
            est.net.load_parameters(os.path.join(path, "net.params"))
            est.trainer.load_states(os.path.join(path, "trainer.states"))
        except (CheckpointCorruptError, OSError, ValueError) as e:
            if resume != "auto":
                raise
            fallbacks += 1
            if _telemetry._enabled:
                _M_VERIFY_FAILURES.inc()
            print(f"mx.resilience: rejecting checkpoint: {e} — falling "
                  "back to the previous one", file=sys.stderr)
            continue
        est.num_epoch = int(state["num_epoch"])
        est.num_batch = int(state["num_batch"])
        key = np.asarray(state["rng_key"],
                         dtype=state.get("rng_dtype", "uint32"))
        _random.set_state(key.reshape(state.get("rng_shape", key.shape)))
        _note_resume(path, est.num_epoch, fallbacks)
        return est.num_epoch
    return None


if _config.get("resilience"):
    install()
