"""mx.memsafe — never-OOM execution.

On a TPU an out-of-memory is an opaque `RESOURCE_EXHAUSTED` that kills the
whole gang mid-run; the information to predict it existed BEFORE dispatch
(`mx.inspect` computes per-executable peak device bytes from XLA's own
`memory_analysis()`, and `device.memory_stats()` reports the capacity).
This module uses that information proactively — "Memory Safe Computations
with XLA Compiler" (PAPERS.md, arxiv 2206.14148) — in four pieces:

  * **pre-flight budget check** — on every jit-cache miss (HybridBlock
    `_call_cached` and the ShardedTrainer step cache), the freshly built
    computation is lowered + compiled ANALYTICALLY and its execution
    footprint beyond the arguments (output + temp - donated bytes) plus
    the resident state (params, optimizer moments, aux, the staged batch
    — the argument buffers, counted exactly once) is compared against
    the device capacity (`device_bytes_limit` knob, else
    `device.memory_stats()['bytes_limit']`). A predicted overrun raises
    `MemoryBudgetError` naming the executable, the predicted peak, the
    capacity, the shortfall, and concrete remediations — BEFORE any device
    dispatch, so no half-donated train state is lost. Every check feeds the
    `memory_headroom_bytes` gauge; headroom below a `memory_headroom_warn`
    fraction of capacity emits a warning event.
  * **graduated remat policies** — `HybridBlock.remat(policy=...)` with
    `"none" | "dots_saveable" | "layers" | "full"` (increasing memory
    savings, increasing recompute), mapped onto `jax.checkpoint` policies;
    the `remat_policy` knob applies a default to every block and the
    per-model `remat=True` config flags keep working as the `"layers"`
    alias.
  * **graceful OOM degradation** — with `oom_recover=auto`, a
    RESOURCE_EXHAUSTED (or pre-flight MemoryBudgetError) at the trainer
    step boundary walks a degradation ladder instead of crashing: escalate
    the remat policy one rung, then shard the optimizer state across the
    data replicas (mx.zero — bit-identical values, (D-1)/D of the
    opt-state bytes back), then halve the effective batch via
    gradient-accumulation microbatching (loss/grad parity preserved up to
    reduction order), re-plan, retry. Each transition is logged to
    telemetry, the diagnostics flight ring, and the post-mortem "memsafe"
    section. `oom_recover=off` (default) keeps today's fail-fast behavior.
  * **auto-fit** — `dataflow.autofit(...)` (+ the `tools/autofit.py` CLI)
    binary-searches the largest batch / `BucketPad` bucket configuration
    whose PREDICTED peak fits the measured capacity, using AOT lowering +
    `memory_analysis()` only — no device step executes.

Cost model: DISABLED (the default) is the production fast path — the
trainer/block hook sites check one module-level bool and fall through; no
analysis compile, no capacity probe, no recovery handler (`ci/run.sh
sanity` asserts it). ENABLED costs one extra lower+compile per jit-cache
miss (served warm from the persistent XLA compile cache) — the same
trade `mx.inspect` makes.
"""
from __future__ import annotations

import sys
import time

from . import _locklint

from . import config as _config
from . import diagnostics as _diagnostics
from . import goodput as _goodput
from . import telemetry as _telemetry

__all__ = [
    "enable", "disable", "enabled", "maybe_enable", "reset",
    "MemoryBudgetError", "SimulatedResourceExhausted", "is_oom",
    "capacity_bytes", "resident_bytes", "compiled_exec_peak",
    "preflight_step", "preflight_jit", "check_budget",
    "POLICIES", "LADDER", "validate_policy", "effective_policy",
    "jax_policy", "policy_marker", "block_wrap_policy",
    "recover_trainer", "note_eager_oom", "transitions", "last_check",
    "last_headroom_bytes", "snapshot",
]

_lock = _locklint.make_rlock("memsafe.state")
_enabled = False              # the fast-path bool; hook sites read it directly
_last_check = None            # dict of the most recent pre-flight check
_transitions = []             # degradation-ladder transitions this process
_oom_events = 0
_warned = set()               # executables already headroom-warned (no spam)

_M_HEADROOM = _telemetry.gauge(
    "memory_headroom_bytes", "device capacity minus the predicted peak of "
    "the last pre-flight-checked executable (resident state + execution "
    "peak); negative would have been an OOM — the check raises instead")
_M_OOM_EVENTS = _telemetry.counter(
    "oom_events_total", "out-of-memory events seen at the trainer boundary: "
    "device RESOURCE_EXHAUSTED plus pre-flight MemoryBudgetError rejections")
_M_OOM_RECOVERIES = _telemetry.counter(
    "oom_recoveries_total", "OOM events survived by the oom_recover=auto "
    "degradation ladder (the step completed after remat escalation and/or "
    "gradient-accumulation microbatching)")


class MemoryBudgetError(RuntimeError):
    """Pre-flight budget check predicted an out-of-memory: the executable's
    predicted peak (execution peak + resident state) exceeds the device
    capacity. Raised BEFORE any device dispatch — no train state has been
    donated or lost. Carries the accounting so tooling (and the
    oom_recover=auto ladder) can act on it."""

    def __init__(self, executable, predicted_bytes, capacity_bytes,
                 exec_peak_bytes=None, resident_bytes=None):
        self.executable = executable
        self.predicted_bytes = int(predicted_bytes)
        self.capacity_bytes = int(capacity_bytes)
        self.exec_peak_bytes = exec_peak_bytes
        self.resident_bytes = resident_bytes
        self.headroom_bytes = int(capacity_bytes) - int(predicted_bytes)
        short = -self.headroom_bytes
        parts = ""
        if exec_peak_bytes is not None and resident_bytes is not None:
            parts = (f" ({_fmt(exec_peak_bytes)} execution peak + "
                     f"{_fmt(resident_bytes)} resident params/optimizer/"
                     "batch)")
        super().__init__(
            f"predicted peak device memory for executable '{executable}' is "
            f"{_fmt(predicted_bytes)}{parts} but device capacity is "
            f"{_fmt(capacity_bytes)} — {_fmt(short)} short. Remediations, "
            "cheapest first: (1) rematerialization — "
            "block.remat(policy='dots_saveable'|'layers'|'full') or the "
            "remat_policy knob trades recompute for activation memory; "
            "(2) shard optimizer state across the data replicas — set "
            "zero=auto (mx.zero) or trainer.set_zero(True): resident "
            "opt-state bytes drop by (D-1)/D with values unchanged; "
            "(3) a smaller batch or BucketPad bucket — dataflow.autofit() "
            "binary-searches the largest configuration that fits. "
            "Set oom_recover=auto to walk these "
            "automatically, or raise device_bytes_limit if the simulated "
            "capacity is wrong.")


class SimulatedResourceExhausted(RuntimeError):
    """Synthetic device OOM raised by the FaultInjector `oom@step:N` spec
    (mx.resilience): the message carries the literal RESOURCE_EXHAUSTED
    marker so it classifies exactly like the real jaxlib error, but no
    device state was touched — every rung of the degradation ladder is
    drivable in CPU tests."""

    def __init__(self, step=None):
        super().__init__(
            "RESOURCE_EXHAUSTED: synthetic out-of-memory injected by "
            f"mx.resilience fault_inject oom@step:{step} (no device "
            "allocation actually failed)")


def _fmt(n):
    """Human bytes for error messages: '1.50 GiB (1610612736 bytes)'."""
    from .util import fmt_bytes
    return fmt_bytes(n, show_raw=True)


def is_oom(exc):
    """True for anything the degradation ladder can act on: a device
    RESOURCE_EXHAUSTED (real jaxlib XlaRuntimeError or the injected
    synthetic) or the pre-flight MemoryBudgetError."""
    return isinstance(exc, MemoryBudgetError) or \
        "RESOURCE_EXHAUSTED" in str(exc)


# ---------------------------------------------------------------------------
# enable / disable
# ---------------------------------------------------------------------------

def enabled():
    """True when memsafe is armed (hook sites read the module global
    `_enabled` directly — this accessor is the public spelling)."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def maybe_enable():
    """Arm memsafe iff the knobs ask for it (`oom_recover=auto` or a
    positive `device_bytes_limit`). Called at trainer construction so
    `mx.config.set(...)` after import still takes effect; one or two dict
    reads, construction-time only — never on the step hot path."""
    if _enabled:
        return True
    if _config.get("oom_recover") == "auto" \
            or int(_config.get("device_bytes_limit")) > 0:
        enable()
    return _enabled


def reset():
    """Drop recorded checks/transitions (tests and run boundaries)."""
    global _last_check, _oom_events
    with _lock:
        _last_check = None
        _oom_events = 0
        del _transitions[:]
        _warned.clear()


# ---------------------------------------------------------------------------
# capacity + accounting
# ---------------------------------------------------------------------------

def capacity_bytes():
    """Device memory capacity in bytes: the `device_bytes_limit` knob when
    positive (CPU CI and tests simulate any capacity this way), else the
    first local device's memory_stats()['bytes_limit'], else None (backend
    reports nothing — CPU — and no check can run). Never cold-inits a
    backend."""
    knob = int(_config.get("device_bytes_limit"))
    if knob > 0:
        return knob
    devs = _diagnostics._jax_devices_if_initialized()
    if not devs:
        return None
    try:
        stats = devs[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def resident_bytes(*trees):
    """Total PER-DEVICE bytes of every array leaf in the given pytrees —
    the state that stays resident on each device while the executable
    runs (params, optimizer moments, aux, the staged batch). A sharded
    array (mx.zero optimizer state, fsdp params, a sharded batch) counts
    only its per-device shard, not the global array: that is what each
    device actually keeps, and what the budget check must compare against
    per-chip capacity. Replicated arrays count in full."""
    import math

    import jax
    total = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            try:
                nbytes = int(leaf.nbytes)
            except Exception:
                # typed PRNG keys (and other extended dtypes) refuse
                # .nbytes; they are a handful of words — negligible
                continue
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                try:
                    shard = sharding.shard_shape(tuple(leaf.shape))
                    nbytes = int(math.prod(shard)) * leaf.dtype.itemsize
                except Exception:
                    pass    # host arrays / odd shardings: global count
            total += nbytes
    return total


def compiled_exec_peak(compiled):
    """Execution-time bytes one compiled executable needs ON TOP of its
    resident argument buffers: output + temp - donated (donated arguments
    alias into outputs, so their reuse is not new memory). The arguments
    themselves are counted exactly once, by resident_bytes — summing
    XLA's full peak (which includes arguments) with the resident state
    would double-count every non-donated buffer and falsely reject
    configurations that fit. None when the backend withholds any
    component. Never raises."""
    from . import inspect as _inspect
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    _arg, out, tmp, alias, peak = _inspect.memory_breakdown(mem)
    if peak is None:
        return None
    return max(0, out + tmp - (alias or 0))


# ---------------------------------------------------------------------------
# pre-flight budget check
# ---------------------------------------------------------------------------

def check_budget(executable, exec_peak, resident, capacity=None):
    """Compare one executable's predicted peak (execution peak + resident
    state) against capacity. Records the check (last_check / the
    memory_headroom_bytes gauge), warns when headroom drops below the
    `memory_headroom_warn` fraction of capacity, and raises
    MemoryBudgetError on a predicted overrun. `exec_peak` None (analysis
    unavailable) checks resident state alone."""
    global _last_check
    capacity = capacity if capacity is not None else capacity_bytes()
    predicted = int(resident or 0) + int(exec_peak or 0)
    headroom = None if capacity is None else int(capacity) - predicted
    with _lock:
        _last_check = {
            "executable": executable,
            "exec_peak_bytes": exec_peak,
            "resident_bytes": int(resident or 0),
            "predicted_bytes": predicted,
            "capacity_bytes": capacity,
            "headroom_bytes": headroom,
            "ts": time.time(),
        }
    if capacity is None:
        return _last_check
    if _telemetry._enabled:
        _M_HEADROOM.set(headroom)
    if headroom < 0:
        _count_oom("budget", executable)
        raise MemoryBudgetError(executable, predicted, capacity,
                                exec_peak_bytes=exec_peak,
                                resident_bytes=int(resident or 0))
    warn_frac = float(_config.get("memory_headroom_warn"))
    if warn_frac > 0 and headroom < warn_frac * capacity \
            and executable not in _warned:
        _warned.add(executable)
        print(f"mx.memsafe: WARNING — executable '{executable}' leaves only "
              f"{_fmt(headroom)} headroom ({headroom / capacity:.1%} of "
              f"capacity, warn threshold {warn_frac:.1%}); one larger bucket "
              "or a fragmentation spike away from RESOURCE_EXHAUSTED",
              file=sys.stderr)
        if _telemetry._enabled:
            _telemetry.event("memsafe_warning", executable=executable,
                             headroom_bytes=headroom,
                             predicted_bytes=predicted,
                             capacity_bytes=capacity)
        if _diagnostics._enabled:
            _diagnostics.record_event(
                "memsafe_warning", executable=executable,
                headroom_bytes=headroom, predicted_bytes=predicted)
    return _last_check


def _analyze(jitted, args, traced=None):
    """AOT lower+compile purely for memory analysis;
    (exec_peak, compiled, error). The real first call deserializes
    this same executable warm from the persistent cache. Never raises — a
    backend that cannot lower out of line degrades the check to
    resident-state accounting. `traced`: a pre-computed jax Traced (from
    mx.check's lint of the same miss) lowered directly, so check+memsafe
    together cost one trace per miss, not two."""
    try:
        if traced is not None:
            try:
                compiled = traced.lower().compile()
                return compiled_exec_peak(compiled), compiled, None
            except Exception:   # stale/unlowerable trace: re-derive
                pass
        compiled = jitted.lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — degrade, never block dispatch
        return None, None, f"{type(e).__name__}: {e}"
    return compiled_exec_peak(compiled), compiled, None


def _preflight(name, key, jitted, args, collectives=None, traced=None):
    """Shared preflight body: with no known capacity there is nothing to
    check, so the (expensive) analysis compile is skipped entirely and
    only the resident accounting is recorded. When the analysis does run
    and mx.inspect is enabled, the compiled object is handed to inspect's
    registry too — the pair then costs ONE extra compile per miss, not
    two (the hook sites skip their own analyze_jit via the returned
    'inspect_recorded' flag). `traced` likewise shares mx.check's trace."""
    capacity = capacity_bytes()
    resident = resident_bytes(args)
    if capacity is None:
        return check_budget(name, None, resident, capacity=None)
    exec_peak, compiled, err = _analyze(jitted, args, traced=traced)
    check = check_budget(name, exec_peak, resident, capacity=capacity)
    if err is not None:
        check["analysis_error"] = err
    if compiled is not None:
        from . import inspect as _inspect
        if _inspect._enabled:
            _inspect.record_compiled(name, _inspect.key_repr(key), compiled,
                                     collectives=collectives)
            check["inspect_recorded"] = True
    return check


def preflight_step(trainer, key, jitted, args, traced=None):
    """Pre-flight budget check for one freshly built ShardedTrainer step
    executable, BEFORE its first dispatch: AOT-analyze the execution
    footprint, add the resident train state + staged batch (== the call
    args), and check the budget. Raises MemoryBudgetError on a predicted
    overrun (nothing was dispatched; donated buffers are intact)."""
    name = f"ShardedTrainer({type(trainer.block).__name__})"
    return _preflight(name, key, jitted, args,
                      collectives=getattr(trainer, "_coll_est", None),
                      traced=traced)


def preflight_jit(name, key, jitted, args, traced=None):
    """Pre-flight check for one freshly built HybridBlock executable
    (forward path): resident state is the parameters + inputs the call
    will hold live."""
    return _preflight(name, key, jitted, args, traced=traced)


def last_check():
    """The most recent pre-flight check's accounting dict (None before
    any)."""
    with _lock:
        return dict(_last_check) if _last_check else None


def last_headroom_bytes():
    """Headroom recorded by the most recent pre-flight check (None before
    any check, or when capacity was unknown)."""
    with _lock:
        return _last_check.get("headroom_bytes") if _last_check else None


# ---------------------------------------------------------------------------
# graduated remat policies
# ---------------------------------------------------------------------------

#: valid policies, in INCREASING memory savings (and recompute cost):
#:   none          — save every intermediate (fastest backward, most HBM)
#:   dots_saveable — jax.checkpoint saving matmul/dot outputs, recomputing
#:                   elementwise/normalization work (the cheap recompute)
#:   layers        — per-layer jax.checkpoint saving ONLY layer boundaries;
#:                   activation memory O(1) in depth (the classic trade)
#:   full          — one checkpoint around the whole stack on top of the
#:                   per-layer ones: only the model inputs survive forward
POLICIES = ("none", "dots_saveable", "layers", "full")

#: the oom_recover=auto escalation order (same tuple; alias for intent)
LADDER = POLICIES


def validate_policy(policy):
    if policy not in POLICIES:
        raise ValueError(
            f"remat policy {policy!r}: expected one of {POLICIES}")
    return policy


def effective_policy(explicit, legacy=False):
    """Resolve the policy for one block: an explicit `.remat(policy=...)`
    wins, else the `remat_policy` knob's global default, else the legacy
    boolean `remat=` config flag as the 'layers' alias, else 'none'."""
    if explicit:
        return validate_policy(explicit)
    knob = _config.get("remat_policy")
    if knob:
        return validate_policy(knob)
    return "layers" if legacy else "none"


def jax_policy(policy):
    """The `jax.checkpoint(policy=...)` argument for one policy name:
    dots_saveable maps to jax's own policy object; layers/full save
    nothing (None) — their structure comes from WHERE the checkpoint is
    applied, not what it saves."""
    if policy == "dots_saveable":
        import jax
        return jax.checkpoint_policies.dots_saveable
    return None


def _policy_block(block):
    """The first block in the subtree that consumes remat policies
    structurally (BERTModel/GPTModel: per-layer / scan-body checkpointing),
    or None when the subtree has no structural handler."""
    if getattr(block, "_remat_handles_policy", False):
        return block
    for child in getattr(block, "_children", {}).values():
        found = _policy_block(child)
        if found is not None:
            return found
    return None


def policy_marker(block):
    """The effective remat policy string for a block tree — what the
    trainer step-cache key carries so a policy change re-jits, and what
    bench reports."""
    b = _policy_block(block) or block
    return effective_policy(getattr(b, "_remat_policy", None),
                            bool(getattr(b, "_remat", False)))


def block_wrap_policy(block):
    """Policy to apply around a block's WHOLE pure function (the generic
    fallback for blocks without structural layer handling), or None. A
    structural handler anywhere in the subtree owns the policy instead —
    wrapping the root too would double-checkpoint."""
    if _policy_block(block) is not None:
        return None
    pol = effective_policy(getattr(block, "_remat_policy", None), False)
    return None if pol == "none" else pol


# ---------------------------------------------------------------------------
# graceful OOM degradation (the ladder)
# ---------------------------------------------------------------------------

def _count_oom(kind, executable=None, step=None):
    global _oom_events
    with _lock:
        _oom_events += 1
    if _telemetry._enabled:
        _M_OOM_EVENTS.inc()
        _telemetry.event("oom", cause=kind, executable=executable, step=step)
    if _diagnostics._enabled:
        _diagnostics.record_event("oom", cause=kind, executable=executable,
                                  step=step)


def _state_intact(trainer):
    """False when the failed dispatch consumed the donated train state (a
    real device OOM mid-execution) — nothing left to retry with."""
    import jax
    leaves = jax.tree_util.tree_leaves(
        (trainer.params, trainer.aux, trainer.opt_state))
    return all(not (hasattr(leaf, "is_deleted") and leaf.is_deleted())
               for leaf in leaves)


def _zero_rung_available(trainer):
    """True when the 'enable mx.zero' rung can fire: the trainer is not
    already sharding optimizer state and its mesh/state could (lazy
    import: memsafe must not pull the parallel package at import)."""
    if getattr(trainer, "_zero", False) or not hasattr(trainer, "set_zero"):
        return False
    try:
        from .parallel import zero as _zero
        return _zero.eligible(trainer)
    except Exception:
        return False


def _next_rung(trainer, data, labels):
    """The next degradation to try: escalate the remat policy one rung
    while possible, then shard the optimizer state across the data
    replicas (mx.zero — a pure layout change, bit-identical values,
    (D-1)/D of the opt-state bytes back), then double the gradient-
    accumulation factor while the batch still divides. None when the
    ladder is exhausted."""
    cur = policy_marker(trainer.block)
    if hasattr(trainer.block, "remat") and cur in LADDER \
            and cur != LADDER[-1]:
        return ("remat", LADDER[LADDER.index(cur) + 1])
    if _zero_rung_available(trainer):
        return ("zero", True)
    data = data if isinstance(data, (list, tuple)) else [data]
    labels = labels if isinstance(labels, (list, tuple)) else [labels]
    new_accum = int(getattr(trainer, "_accum", 1)) * 2
    shapes = [tuple(getattr(b, "shape", ())) for b in
              list(data) + list(labels)]
    # every array needs a splittable leading dim — a 0-d scalar anywhere
    # makes _build_step reject the accum rung, so don't propose it
    if shapes and new_accum <= 256 and \
            all(s and s[0] % new_accum == 0 and s[0] // new_accum >= 1
                for s in shapes):
        return ("accum", new_accum)
    return None


def _note_transition(trainer, kind, value, step):
    entry = {"kind": kind, "value": value, "step": step, "ts": time.time(),
             "policy": policy_marker(trainer.block),
             "accum": int(getattr(trainer, "_accum", 1)),
             "zero": bool(getattr(trainer, "_zero", False))}
    with _lock:
        _transitions.append(entry)
    if kind == "remat":
        what = f"remat policy -> {value!r}"
    elif kind == "zero":
        what = ("optimizer-state sharding ON (mx.zero: reduce-scatter/"
                "all-gather weight update; values unchanged, resident "
                "opt-state bytes /= data extent)")
    else:
        what = (f"gradient accumulation x{value} (microbatch = batch/"
                f"{value})")
    print(f"mx.memsafe: degradation ladder at step {step}: {what}",
          file=sys.stderr)
    if _telemetry._enabled:
        _telemetry.event("memsafe", action=kind, value=value, step=step)
    if _diagnostics._enabled:
        _diagnostics.record_event("memsafe", action=kind, value=value,
                                  step=step)


def recover_trainer(trainer, exc, data, labels, fence_every):
    """Walk the degradation ladder after an OOM at the trainer step
    boundary (called by ShardedTrainer._step_impl; memsafe enabled and
    is_oom(exc) already established). With oom_recover != 'auto' the
    original error propagates untouched (fail-fast). Otherwise: escalate
    remat, then halve the batch via gradient accumulation, re-plan (the
    step cache re-jits under the new key) and retry, until the step
    completes or the ladder is exhausted.

    Note on RNG: a failed attempt may have consumed a step key from the
    global stream before dying, so a recovered DROPOUT run's draws can
    shift relative to an uninterrupted one — losses stay valid, they are
    just a different sample. Deterministic-parity tests run dropout-free."""
    step = int(trainer.num_update) + 1
    t_rung = time.perf_counter() if _goodput._enabled else None
    if not isinstance(exc, MemoryBudgetError):
        # pre-flight rejections already counted themselves in check_budget
        _count_oom("device", step=step)
    if _config.get("oom_recover") != "auto":
        raise exc
    if not _state_intact(trainer):
        # the failed dispatch consumed donated buffers: values are gone,
        # a retry would compute garbage. The pre-flight check exists to
        # catch this case BEFORE dispatch.
        raise RuntimeError(
            "mx.memsafe: the OOM-failed dispatch consumed the trainer's "
            "donated train state — cannot retry in place. Set "
            "device_bytes_limit (or run on a backend with memory_stats) "
            "so the pre-flight budget check rejects the configuration "
            "before dispatch, or restore from the last checkpoint."
        ) from exc
    while True:
        rung = _next_rung(trainer, data, labels)
        if rung is None:
            try:
                exc.add_note("mx.memsafe: degradation ladder exhausted "
                             "(remat at 'full', batch no longer divisible)")
            except AttributeError:  # pragma: no cover - py<3.11
                pass
            raise exc
        kind, value = rung
        if kind == "remat":
            trainer.block.remat(value)
        elif kind == "zero":
            trainer.set_zero(True)
        else:
            trainer.set_grad_accum(value)
        trainer._step_cache.clear()
        _note_transition(trainer, kind, value, step)
        if _goodput._enabled:
            # the ladder walk so far (failed attempt + re-plan) is
            # badput:oom_recovery, and so is the retry's re-jit below
            # (note_oom_begin re-categorizes its cache-miss interval)
            now = time.perf_counter()
            _goodput.note("oom_recovery", t_rung if t_rung is not None
                          else now, now, step=step, rung=kind)
            t_rung = now
            _goodput.note_oom_begin(step)
        try:
            out = trainer._step_once(data, labels, fence_every)
        except Exception as e2:  # noqa: BLE001 — classified below
            if not is_oom(e2):
                raise
            if not isinstance(e2, MemoryBudgetError):
                _count_oom("device", step=step)
            if not _state_intact(trainer):
                raise
            exc = e2
            continue
        if _telemetry._enabled:
            _M_OOM_RECOVERIES.inc()
        print(f"mx.memsafe: step {step} recovered (policy="
              f"{policy_marker(trainer.block)!r}, zero="
              f"{bool(getattr(trainer, '_zero', False))}, grad "
              f"accumulation x{getattr(trainer, '_accum', 1)})",
              file=sys.stderr)
        return out


def note_eager_oom(exc, step=None):
    """Record an OOM on the eager gluon Trainer path (which cannot
    microbatch a tape that already ran) and annotate the exception with
    the remediation story before it propagates."""
    _count_oom("eager", step=step)
    try:
        exc.add_note(
            "mx.memsafe: eager-path OOM — the gluon Trainer cannot degrade "
            "a step whose tape already ran. Remat the model "
            "(block.remat(policy=...)), reduce the batch, or move to "
            "parallel.ShardedTrainer where oom_recover=auto walks the "
            "degradation ladder automatically.")
    except AttributeError:  # pragma: no cover - py<3.11
        pass


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def transitions():
    """Degradation-ladder transitions recorded this process (copies)."""
    with _lock:
        return [dict(t) for t in _transitions]


def snapshot():
    """Plain-data summary for the diagnostics post-mortem 'memsafe'
    section: the last pre-flight check, every ladder transition, and the
    OOM event count."""
    with _lock:
        return {
            "oom_events": _oom_events,
            "last_check": dict(_last_check) if _last_check else None,
            "transitions": [dict(t) for t in _transitions],
        }


maybe_enable()
