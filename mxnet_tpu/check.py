"""mx.check — static graph & concurrency analysis.

Every subsystem before this one found its bugs at RUNTIME: two PRs
shipped the same direct-`jax.shard_map` import breakage, the launch
supervisor deadlocked on a blocking wait inside a signal handler, and
donation/retrace/replication hazards surface only after they cost a
recompile or an OOM. Relay/TVM (PAPERS.md) make the argument that owning
a graph-level IR means owning ANALYSES over it; this module applies that
to the three IRs this framework already has — the traced jaxpr, the
sharding specs, and the host-side lock graph — turning those recurring
runtime failure classes into pre-merge static findings. Three layers:

  * **graph lint** — at every jit-cache miss (the same hook sites
    telemetry/inspect/memsafe share in `gluon/block.py`,
    `parallel/trainer.py`, and `models/_decode.py`), the fresh
    computation is re-traced (trace only — no compile) and its
    ClosedJaxpr walked for: large closure-captured constants baked into
    the executable (`large-constant`), un-donated state threading and
    donate=False trainers (`donation-miss`, cross-checked against
    mx.memsafe's resident-bytes accounting), silent bf16/f16 -> f32/f64
    promotions of whole activation tensors (`dtype-promotion`),
    statically-predictable retrace hazards — a signature component
    observed to keep varying (`retrace-hazard`, the BEFORE-the-fact
    complement of telemetry's recompile-cause diff) — and degenerate
    sharding: large fully-replicated params/batches on a multi-device
    mesh (`degenerate-sharding`; remediated by the now-real `zero=auto`
    knob — mx.zero optimizer-state sharding — and quiet on a zero'd
    trainer).
  * **concurrency analysis** — `mxnet_tpu/_locklint.py`: the
    instrumented-lock wrapper adopted by telemetry, diagnostics,
    dataflow's prefetcher, resilience, inspect, memsafe, profiler, and
    tools/launch.py. Under `MXNET_TPU_CHECK_THREADS=1` (tsan-lite, run
    over the threaded unit tests by the CI `static` stage) it records
    the acquisition-order graph, raises on a cycle with BOTH acquisition
    stacks (`lock-order-cycle`), and asserts guarded shared structures
    are mutated under their lock (`unguarded-mutation`).
  * **AST rules** — `tools/lint_rules.py`, run as the CI `static` stage:
    repo-specific source checkers for the two shipped bug classes
    (direct `shard_map` imports outside `parallel/_compat.py`; blocking
    calls inside signal handlers) plus raw `threading.Lock()` in
    instrumented modules and wall-clock calls inside jitted step
    functions.

Findings surface as structured records (`tools/check_graph.py` CLI over
`check_dir` dumps), the `check_findings_total{rule=...}` telemetry
counter, diagnostics ring events, and `bench.py`'s `check_findings`
field. The `check` knob is `off|warn|error`: off (default) is the
zero-overhead fast path — hook sites reduce to one module-bool check,
no trace, no registry (asserted by ci/run.sh sanity); warn reports;
error raises `CheckError` naming the rule, location, and remediation.
Suppress a finding inline with `with mx.check.suppress("rule"): ...`
(AST rules use a `# mx.check: disable=rule` comment instead).
"""
from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import math
import os
import sys
import time

from . import _locklint
from . import config as _config
from . import diagnostics as _diagnostics
from . import telemetry as _telemetry
from ._locklint import (LockOrderError, make_lock, make_rlock,  # noqa: F401
                        guarded_dict)
from .util import fmt_bytes as _fmt_bytes  # shared with memsafe._fmt

__all__ = [
    "enable", "disable", "enabled", "maybe_enable", "reset",
    "CheckError", "RULES", "report_finding", "suppress",
    "check_jit", "check_step", "lint_jaxpr", "lint_paging",
    "note_signature",
    "note_scalar", "findings", "thread_findings", "snapshot", "dump",
    "make_lock", "make_rlock", "LockOrderError",
]

#: rule catalog — name -> one-line description (README + report CLI)
RULES = {
    "large-constant": "closure-captured array baked into an executable as "
                      "a constant (re-staged per compile, defeats "
                      "donation) at/above check_large_const_bytes",
    "donation-miss": "state threaded through a jitted call (identical "
                     "input/output shape+dtype) or a donate=False trainer "
                     "— the buffers double-buffer every call",
    "dtype-promotion": "silent bf16/f16 -> f32/f64 upcast of a whole "
                       "tensor at/above check_promotion_min_bytes (a "
                       "non-weak f32 scalar promotes; python scalars "
                       "stay weak and do not)",
    "retrace-hazard": "a signature component (input-shape axis or baked "
                      "python scalar) observed varying across "
                      "check_retrace_limit compiles — and predicted to "
                      "keep varying, one full recompile each",
    "degenerate-sharding": "large fully-replicated params or batch "
                           "inputs on a mesh whose data axes span >1 "
                           "device (every device holds the full array)",
    "degenerate-paging": "a server whose page size exceeds its "
                         "smallest bucket (prefix sharing can never "
                         "engage) or whose drafter's vocabulary differs "
                         "from the target's (speculative proposals are "
                         "meaningless token ids)",
    "lock-order-cycle": "two contexts acquire the same locks in opposite "
                        "orders (tsan-lite; reported with both "
                        "acquisition stacks)",
    "unguarded-mutation": "guarded shared structure mutated without "
                          "holding its lock (tsan-lite)",
}

_lock = make_rlock("check.registry")
_enabled = False              # the fast-path bool; hook sites read it directly
_findings = []                # finding dicts, append-only this process
_fired = set()                # (rule, dedupe-key) already reported
_sig_axis = {}                # (owner, name, input, axis, rest) -> set(values)
_sig_scalar = {}              # (owner, name, slot) -> set(values)
_SIG_CAP = 4096               # drop-oldest bound on the signature history
_suppressed = set()           # rules currently suppressed (suppress())
_owner_counter = itertools.count(1)

_M_FINDINGS = _telemetry.counter(
    "check_findings_total", "mx.check static-analysis findings, labeled by "
    "rule (graph lint at jit-cache misses + tsan-lite concurrency "
    "findings)")


class CheckError(RuntimeError):
    """A finding under check=error. Carries the finding dict; the message
    names the rule, the location, and the remediation."""

    def __init__(self, finding):
        self.finding = dict(finding)
        super().__init__(
            f"mx.check [{finding['rule']}] at {finding['location']}: "
            f"{finding['message']} Remediation: {finding['remediation']} "
            "(suppress with `with mx.check.suppress("
            f"{finding['rule']!r}): ...`, relax the rule's threshold "
            "knob, or set check=warn)")


# ---------------------------------------------------------------------------
# enable / disable
# ---------------------------------------------------------------------------

def enabled():
    """True when graph lint is armed (hook sites read the module global
    `_enabled` directly — this accessor is the public spelling)."""
    return _enabled


def enable(mode=None):
    """Arm graph lint; `mode` ('warn'|'error') also sets the knob."""
    global _enabled
    if mode is not None:
        _config.set("check", mode)
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def maybe_enable():
    """Arm iff the `check` knob asks (construction-time config read only —
    the step hot path keeps its single module-bool check)."""
    if _enabled:
        return True
    if _config.get("check") != "off":
        enable()
    return _enabled


def reset():
    """Drop findings and signature history (tests and run boundaries);
    the lock-order graph resets through _locklint.reset()."""
    with _lock:
        del _findings[:]
        _fired.clear()
        _sig_axis.clear()
        _sig_scalar.clear()


def owner_token(obj):
    """A process-unique identity token for `obj`, assigned once and
    stored on the instance. Raw id() would be wrong here: CPython reuses
    addresses after GC, so a sweep loop constructing trainers would
    inherit dead instances' retrace histories (false hazards) or their
    dedupe entries (suppressed real ones)."""
    tok = getattr(obj, "_mx_check_token", None)
    if tok is None:
        tok = next(_owner_counter)
        try:
            obj._mx_check_token = tok
        except Exception:
            pass     # unsettable (slots): the token is still unique
    return tok


def _cap_history(d):
    """Drop-oldest bound (called under _lock): the signature history must
    not grow without limit in a long-lived process compiling many
    blocks — dict insertion order makes the first key the oldest."""
    while len(d) > _SIG_CAP:
        del d[next(iter(d))]


@contextlib.contextmanager
def suppress(*rules):
    """Inline suppression: findings for `rules` inside the block are
    dropped (not recorded, not raised). The README documents this as the
    per-call-site escape hatch; prefer fixing or re-thresholding."""
    with _lock:
        added = [r for r in rules if r not in _suppressed]
        _suppressed.update(added)
    try:
        yield
    finally:
        with _lock:
            _suppressed.difference_update(added)


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------

def report_finding(rule, location, message, remediation, dedupe=None,
                   **details):
    """Record one finding: registry + check_findings_total{rule=} +
    diagnostics ring + stderr (warn mode) or CheckError (error mode).
    `dedupe` bounds repeats: the same (rule, dedupe) RECORDS once — but
    under check=error every recurrence still raises (the hazard is still
    there; a dedupe that swallowed the raise would let the evicted-and-
    recompiled executable dispatch on retry). Returns the finding dict,
    or None when deduped (warn) / suppressed."""
    mode = _config.get("check")
    with _lock:
        if rule in _suppressed:
            return None
        fkey = (rule, dedupe if dedupe is not None else location)
        repeat = fkey in _fired
        finding = {"rule": rule, "location": location, "message": message,
                   "remediation": remediation, "ts": time.time()}
        if details:
            finding["details"] = details
        if repeat:
            if mode == "error":
                raise CheckError(finding)
            return None
        _fired.add(fkey)
        _findings.append(finding)
    if _telemetry._enabled:
        _M_FINDINGS.labels(rule=rule).inc()
        _telemetry.event("check", rule=rule, location=location,
                         message=message)
    if _diagnostics._enabled:
        _diagnostics.record_event("check", rule=rule, location=location,
                                  message=message)
    if mode == "error":
        _maybe_dump()
        raise CheckError(finding)
    print(f"mx.check: [{rule}] {location}: {message} — {remediation}",
          file=sys.stderr)
    _maybe_dump()
    return finding


def findings(rule=None):
    """Graph-lint findings recorded this process (copies)."""
    with _lock:
        out = [dict(f) for f in _findings]
    return [f for f in out if rule is None or f["rule"] == rule]


def thread_findings():
    """Concurrency findings from the tsan-lite lock layer (cycles +
    unguarded mutations), as finding dicts in the same shape."""
    out = []
    for f in _locklint.findings():
        rule = f.get("rule", "lock-order-cycle")
        if rule == "unguarded-mutation":
            location = f.get("structure", "?")
            remediation = (f"take the guard lock "
                           f"'{f.get('guard', '?')}' around the "
                           "mutation (every other mutation site of this "
                           "structure already does)")
        else:
            locks = f.get("locks")
            location = ",".join(locks) if isinstance(locks, list) \
                else str(f.get("lock", "?"))
            remediation = ("make the acquisition order consistent (or "
                           "drop to one lock); for signal paths, set a "
                           "flag and do the work on the main loop")
        out.append({
            "rule": rule,
            "location": location,
            "message": f.get("message", ""),
            "remediation": remediation,
            "details": {k: v for k, v in f.items()
                        if k not in ("rule", "message")},
        })
    return out


# ---------------------------------------------------------------------------
# jaxpr access
# ---------------------------------------------------------------------------

def trace_jit(jitted, args):
    """The jax Traced object for `jitted` at `args` (abstract trace, no
    compile), or None when the computation cannot be traced out of line.
    The hook sites call this ONCE and hand the result to BOTH this
    module's lint and memsafe's preflight (which lowers from it instead
    of re-tracing) — check+memsafe together then cost one trace per
    miss, not two."""
    try:
        return jitted.trace(*args)
    except Exception:
        return None


def _closed_jaxpr(jitted, args, traced=None):
    """ClosedJaxpr of `jitted` at `args` — trace only, no compile; None
    when the computation cannot be traced out of line (degrade, never
    block dispatch). `traced`: a pre-computed trace_jit result to reuse."""
    try:
        if traced is None:
            traced = jitted.trace(*args)
        return traced.jaxpr
    except Exception:
        pass
    try:
        import jax
        closed = jax.make_jaxpr(jitted)(*args)
        # make_jaxpr on a jitted fn wraps everything in one pjit eqn
        if len(closed.jaxpr.eqns) == 1 and \
                "jaxpr" in closed.jaxpr.eqns[0].params:
            return closed.jaxpr.eqns[0].params["jaxpr"]
        return closed
    except Exception:
        return None


def _walk_jaxprs(jaxpr):
    """Yield `jaxpr` and every sub-jaxpr reachable through eqn params
    (pjit/remat/scan/while/cond bodies), as (jaxpr, consts) pairs."""
    seen = []
    todo = [jaxpr]
    while todo:
        j = todo.pop()
        closed_consts = ()
        if hasattr(j, "jaxpr"):          # ClosedJaxpr
            closed_consts = tuple(getattr(j, "consts", ()) or ())
            j = j.jaxpr
        if any(j is s for s in seen):
            continue
        seen.append(j)
        yield j, closed_consts
        for eqn in j.eqns:
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        todo.append(sub)


def _aval_nbytes(aval):
    try:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# graph-lint rules
# ---------------------------------------------------------------------------

_SMALL_FLOATS = ("bfloat16", "float16")
_BIG_FLOATS = ("float32", "float64")


def lint_jaxpr(name, closed, donated_flat=(), can_donate=False):
    """Walk one traced computation: large baked constants, silent dtype
    promotions, and — at call sites that CAN donate (`can_donate`: the
    trainer step, jit_flat_step) — un-donated state threading (identical
    input/output avals). The plain HybridBlock forward path must NOT run
    the threading detector: `y = f(x)` with y sharing x's shape+dtype is
    every residual/layernorm block, nothing is threaded, and the
    `net(x)` surface offers no way to donate anyway. `donated_flat`:
    flat invar indices the executable donates."""
    if closed is None:
        return
    const_thresh = int(_config.get("check_large_const_bytes"))
    promo_thresh = int(_config.get("check_promotion_min_bytes"))
    donate_thresh = int(_config.get("check_donation_min_bytes")) \
        if can_donate else 0

    top = True
    for jaxpr, consts in _walk_jaxprs(closed):
        if const_thresh > 0:
            for c in consts:
                # size x itemsize, not .nbytes: closure constants arrive
                # as jax TypedNdArray literals, which have no nbytes
                nbytes = _aval_nbytes(c)
                if nbytes >= const_thresh:
                    report_finding(
                        "large-constant", name,
                        f"a {_fmt_bytes(nbytes)} "
                        f"{getattr(c, 'dtype', '?')} array of shape "
                        f"{tuple(getattr(c, 'shape', ()))} is baked into "
                        "the executable as a closure-captured constant "
                        "(not a parameter/argument): it is re-staged with "
                        "every compile of this signature and can never be "
                        "donated or sharded.",
                        "pass the array as an argument (register it as a "
                        "Parameter with grad_req='null', or thread it "
                        "through the call), or shrink it below the "
                        "check_large_const_bytes knob",
                        dedupe=(name, "const",
                                tuple(getattr(c, "shape", ())),
                                str(getattr(c, "dtype", "?"))),
                        nbytes=nbytes)
        if promo_thresh > 0:
            for eqn in jaxpr.eqns:
                if eqn.primitive.name != "convert_element_type":
                    continue
                try:
                    src = str(eqn.invars[0].aval.dtype)
                    dst = str(eqn.params.get("new_dtype"))
                    out_aval = eqn.outvars[0].aval
                except Exception:
                    continue
                if src in _SMALL_FLOATS and dst in _BIG_FLOATS:
                    nbytes = _aval_nbytes(out_aval)
                    if nbytes >= promo_thresh:
                        report_finding(
                            "dtype-promotion", name,
                            f"a {src} tensor of shape "
                            f"{tuple(out_aval.shape)} is upcast to {dst} "
                            f"({_fmt_bytes(nbytes)} after the upcast) "
                            "inside the computation — usually a non-weak "
                            "f32 scalar (np.float32(...), an f32 array "
                            "constant) silently promoting the whole "
                            "activation; the loss path then runs at "
                            f"{dst} bandwidth.",
                            "use python scalars (weakly typed: they cast "
                            "DOWN to the tensor dtype) or an explicit "
                            ".astype at the intended boundary; raise "
                            "check_promotion_min_bytes if this upcast is "
                            "deliberate",
                            dedupe=(name, "promo", tuple(out_aval.shape),
                                    src, dst),
                            nbytes=nbytes, src=src, dst=dst)
        if top and donate_thresh > 0:
            top = False
            _lint_state_threading(name, jaxpr, donated_flat, donate_thresh)


def _lint_state_threading(name, jaxpr, donated_flat, thresh):
    """Un-donated state threading: an input buffer whose shape+dtype
    exactly matches an output (KV caches, moments, counters threaded
    through the call) and is not donated is double-buffered on every
    call — the executable writes the new state next to the live old one."""
    donated_flat = set(donated_flat or ())
    out_avals = {}
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "shape", None) is not None:
            key = (tuple(aval.shape), str(aval.dtype))
            out_avals[key] = out_avals.get(key, 0) + 1
    hits = []
    total = 0
    for i, v in enumerate(jaxpr.invars):
        if i in donated_flat:
            continue
        aval = getattr(v, "aval", None)
        if aval is None or getattr(aval, "shape", None) is None:
            continue
        key = (tuple(aval.shape), str(aval.dtype))
        nbytes = _aval_nbytes(aval)
        if out_avals.get(key, 0) > 0 and nbytes >= thresh:
            out_avals[key] -= 1     # pair each output at most once
            hits.append((i, key, nbytes))
            total += nbytes
    if hits:
        shapes = ", ".join(f"arg{i} {k[0]}/{k[1]} ({_fmt_bytes(n)})"
                           for i, k, n in hits[:4])
        more = f" (+{len(hits) - 4} more)" if len(hits) > 4 else ""
        report_finding(
            "donation-miss", name,
            f"{len(hits)} un-donated input buffer(s) totalling "
            f"{_fmt_bytes(total)} have identical shape+dtype outputs — "
            f"state threaded through the call ({shapes}{more}) is "
            "double-buffered: the executable allocates the new state "
            "while the old buffers stay live.",
            "donate the state arguments "
            "(jax.jit(..., donate_argnums=...)); the caller must then "
            "stop reusing the passed-in buffers",
            dedupe=(name, "donate"),
            nbytes=total, n_buffers=len(hits))


def note_signature(name, shapes, owner=None):
    """Record one compile signature and fire `retrace-hazard` when ONE
    axis of one input has taken `check_retrace_limit` distinct values
    with everything else fixed: each value is a full recompile and the
    axis is predicted to keep varying (the BEFORE-the-fact complement of
    telemetry's recompile-cause diff). `owner` is the INSTANCE identity
    (the hook sites pass id(block)/id(trainer)): two blocks of the same
    class each compiling once must not pool into one false hazard —
    only one cache re-jitting is a hazard."""
    limit = int(_config.get("check_retrace_limit"))
    if limit <= 0:
        return
    owner = owner if owner is not None else name
    shapes = tuple(tuple(s) for s in shapes)
    with _lock:
        for i, shape in enumerate(shapes):
            for ax, val in enumerate(shape):
                rest = (shapes[:i],
                        shape[:ax] + ("*",) + shape[ax + 1:],
                        shapes[i + 1:])
                key = (owner, name, i, ax, rest)
                seen = _sig_axis.setdefault(key, set())
                seen.add(val)
                _cap_history(_sig_axis)
                if len(seen) >= limit and not _looks_bucketed(seen):
                    vals = sorted(seen)
                    report_finding(
                        "retrace-hazard", name,
                        f"input[{i}] axis {ax} has compiled at "
                        f"{len(seen)} distinct sizes "
                        f"({vals[:6]}{'...' if len(vals) > 6 else ''}) "
                        "with every other signature component fixed — "
                        "each new size is a full XLA recompile, and this "
                        "axis is predicted to keep varying (varlen "
                        "inputs).",
                        "bucket the axis with dataflow.BucketPad (bounded "
                        "executable count, padding overhead visible in "
                        "bucket_pad_waste_ratio) or pad to a fixed shape",
                        dedupe=(owner, name, "axis", i, ax),
                        input=i, axis=ax, sizes=vals[:16])


def _looks_bucketed(values):
    """True when every observed axis size is a power of two at or above
    the bucket_pad_min floor — the exact output of dataflow.BucketPad's
    default policy. A stream that FOLLOWED the retrace-hazard remediation
    must not keep tripping the rule: its executable count is bounded by
    the bucket set, which is the point. Explicit non-pow2 bucket lists
    are rarer; suppress() or a higher check_retrace_limit covers them."""
    try:
        floor = max(1, int(_config.get("bucket_pad_min")))
    except Exception:
        floor = 1
    return all(isinstance(v, int) and v >= floor and v > 0
               and (v & (v - 1)) == 0 for v in values)


def note_scalar(name, slot, value, owner=None):
    """Record one baked-scalar signature component (e.g. the in-jit lr
    key) and fire `retrace-hazard` once it has taken
    `check_retrace_limit` distinct values: the python scalar is baked
    into the executable, so every new value re-jits. `owner` is the
    instance identity, like note_signature's."""
    limit = int(_config.get("check_retrace_limit"))
    if limit <= 0 or value is None:
        return
    owner = owner if owner is not None else name
    with _lock:
        try:
            seen = _sig_scalar.setdefault((owner, name, slot), set())
            seen.add(value)
        except TypeError:
            return      # unhashable component: nothing to track
        _cap_history(_sig_scalar)
        n = len(seen)
    if n >= limit:
        report_finding(
            "retrace-hazard", name,
            f"python-scalar signature component '{slot}' has compiled at "
            f"{n} distinct values — the scalar is baked into the "
            "executable (a mutated learning rate / schedule "
            "hyperparameter), so every new value is a full re-jit.",
            "move the scalar into the computation (a traceable "
            "lr_scheduler computes lr IN-jit; see "
            "FunctionalOptimizer.lr_traced) or stop mutating it per step",
            dedupe=(owner, name, "scalar", slot), slot=slot, values=n)


# ---------------------------------------------------------------------------
# hook entry points (gluon/block.py, parallel/trainer.py, models/_decode.py)
# ---------------------------------------------------------------------------

def _flat_donated(args, donate_argnums):
    """Flat invar indices covered by `donate_argnums` over `args` (jit
    flattens arguments in order)."""
    import jax
    donated = set()
    flat = 0
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate_argnums:
            donated.update(range(flat, flat + n))
        flat += n
    return donated


def check_jit(name, key, jitted, args, donate_argnums=(), owner=None,
              traced=None, can_donate=False):
    """Graph lint for one freshly built HybridBlock / decode-step
    executable (forward path). Trace-only — no compile; failures degrade
    to a skipped lint, never a blocked dispatch. CheckError (check=error)
    propagates to the caller, which must evict the rejected cache entry.
    `owner`: the block instance's identity for retrace history;
    `traced`: a pre-computed trace_jit result to reuse; `can_donate`:
    True only for call sites whose API can express donation (e.g.
    jit_flat_step's donate_state) — arms the state-threading detector."""
    closed = _closed_jaxpr(jitted, args, traced=traced)
    donated = _flat_donated(args, set(donate_argnums)) \
        if donate_argnums and closed is not None else ()
    lint_jaxpr(name, closed, donated_flat=donated, can_donate=can_donate)
    # signature history uses the CACHE KEY's shape component — the stable
    # spelling of what re-jits
    if isinstance(key, tuple) and key and isinstance(key[0], tuple):
        note_signature(name, [s for s, _ in key[0]
                              if isinstance(s, tuple)], owner=owner)
    return True


def check_step(trainer, key, jitted, args, batch=(), traced=None):
    """Graph lint for one freshly built ShardedTrainer step executable:
    the jaxpr rules plus the trainer-level donation and sharding checks.
    `traced`: a pre-computed trace_jit result to reuse."""
    name = f"ShardedTrainer({type(trainer.block).__name__})"
    # donation: donate=False double-buffers params + optimizer state —
    # quantified with the same resident-bytes accounting memsafe budgets
    # with, so the two subsystems can never disagree about the cost
    if not getattr(trainer, "_donate", True):
        from . import memsafe as _memsafe
        nbytes = _memsafe.resident_bytes(
            (trainer.params, trainer.opt_state))
        report_finding(
            "donation-miss", name,
            f"trainer constructed with donate=False: params + optimizer "
            f"state ({_fmt_bytes(nbytes)} resident) are passed into the "
            "jitted step but NOT donated, so XLA allocates the updated "
            "copies next to the live old ones — double-buffered train "
            "state every step (the same bytes mx.memsafe budgets as "
            "resident).",
            "construct ShardedTrainer with donate=True (the default) "
            "unless an external reference to the pre-step buffers is "
            "genuinely required",
            dedupe=(name, "donate=False"), nbytes=int(nbytes))
    closed = _closed_jaxpr(jitted, args, traced=traced)
    if getattr(trainer, "_donate", True):
        # params/aux/opt/t are donated (argnums 0-3): exclude them from
        # the state-threading detector or every trainer would fire
        donated = _flat_donated(args, {0, 1, 2, 3}) \
            if closed is not None else ()
    else:
        donated = ()
    lint_jaxpr(name, closed, donated_flat=donated, can_donate=True)
    _lint_sharding(trainer, name, key, batch)
    # retrace history: the shape component and the baked-scalar (in-jit
    # lr) component of the step-cache key, per trainer INSTANCE (a sweep
    # constructing many trainers, each compiling once, is not a hazard;
    # owner_token, not id() — CPython reuses addresses after GC)
    if isinstance(key, tuple) and len(key) > 3:
        tok = owner_token(trainer)
        note_signature(name, key[2], owner=tok)
        if isinstance(key[3], (int, float)):
            note_scalar(name, "learning-rate", key[3], owner=tok)
        elif isinstance(key[3], tuple):
            note_scalar(name, "lr-schedule-hyperparams", key[3],
                        owner=tok)
    return True


def _lint_sharding(trainer, name, key, batch):
    """Degenerate sharding: on a mesh whose data axes span >1 device,
    large fully-replicated trained params (every device holds and
    updates the full array — the mx.zero gap) or fully-replicated batch
    inputs (every device receives the full batch: the implicit
    all-gather a sharded step should never contain)."""
    thresh = int(_config.get("check_replicated_min_bytes"))
    if thresh <= 0:
        return
    mesh = getattr(trainer, "mesh", None)
    if mesh is None:
        return
    try:
        extent = int(mesh.shape.get("dp", 1)) * \
            int(mesh.shape.get("fsdp", 1))
    except Exception:
        return
    if extent <= 1:
        return
    if getattr(trainer, "param_mode", "replicate") == "replicate" \
            and not getattr(trainer, "_zero", False):
        # a zero'd trainer already shards its optimizer state and updates
        # per-shard (reduce-scatter/all-gather weight update) — exactly
        # the remediation this finding names, so it goes quiet
        from . import memsafe as _memsafe
        pbytes = int(_memsafe.resident_bytes(
            (trainer.params, trainer.opt_state)))
        if pbytes >= thresh:
            report_finding(
                "degenerate-sharding", name,
                f"params + optimizer state ({_fmt_bytes(pbytes)}) are "
                f"fully replicated across {extent} data-parallel "
                "devices: every device holds and updates the complete "
                "train state.",
                "set zero='auto' (mx.zero: shard optimizer state across "
                "the data replicas with a reduce-scatter/all-gather "
                "weight update — resident opt-state bytes /= data "
                "extent, values unchanged), or param_mode='fsdp' to "
                "shard params + optimizer state over the data axes; "
                "raise check_replicated_min_bytes if this model is "
                "small enough to replicate deliberately",
                dedupe=(name, "replicated-params"),
                nbytes=pbytes, devices=extent)
    # batch inputs: re-derive the shardings the step will use
    try:
        n_data, n_label, shapes = int(key[0]), int(key[1]), key[2]
        shardings = trainer._batch_shardings(n_data, n_label, shapes)
    except Exception:
        return
    for i, (sh, arr) in enumerate(zip(shardings, batch or ())):
        spec = getattr(sh, "spec", None)
        axes = set()
        for entry in (spec or ()):
            if entry is None:
                continue
            axes.update(entry if isinstance(entry, tuple) else (entry,))
        nbytes = int(getattr(arr, "nbytes", 0) or 0)
        if not axes and nbytes >= thresh:
            report_finding(
                "degenerate-sharding", name,
                f"batch input[{i}] ({_fmt_bytes(nbytes)}, shape "
                f"{tuple(getattr(arr, 'shape', ()))}) is fully "
                f"replicated across the {extent}-device data mesh: "
                "every device receives and stages the whole array.",
                "give the input a sharded PartitionSpec via "
                "data_specs/label_specs (batch axis on the data axes), "
                "or raise check_replicated_min_bytes for genuinely "
                "replicated inputs (lookup tables)",
                dedupe=(name, "replicated-batch", i),
                input=i, nbytes=nbytes, devices=extent)


def lint_paging(location, page_size, min_bucket, target_vocab,
                drafter_vocab=None):
    """Degenerate paging configuration lint, run once at
    Server construction (mirrors `degenerate-sharding`: a setup that
    silently voids the feature's benefit rather than crashing).

    Two shapes: (1) a page size larger than the smallest bucket — every
    short request rounds its bucket UP to one page, prompts shorter
    than a page never produce a full (shareable) block, and the prefix
    tree can never engage for exactly the traffic paging targets;
    (2) a speculative drafter whose vocabulary differs from the
    target's — its argmax proposals index a different token space, so
    every verify round rejects at the first token and the extra
    dispatches are pure overhead (or worse: out-of-range ids)."""
    if not _enabled:
        return
    if int(page_size) > int(min_bucket):
        report_finding(
            "degenerate-paging", location,
            f"pages_page_size {page_size} exceeds the smallest serve "
            f"bucket {min_bucket}: every request shorter than a page "
            "rounds up to a full page and never yields a sharable "
            "prefix block — the prefix tree cannot engage for short "
            "traffic.",
            "lower pages_page_size to at most the smallest bucket (a "
            "divisor of the common bucket sizes keeps tables dense), "
            "or raise bucket_pad_min/serve_buckets so the smallest "
            "bucket covers at least one page",
            dedupe=(location, "page-size"),
            page_size=int(page_size), min_bucket=int(min_bucket))
    if drafter_vocab is not None \
            and int(drafter_vocab) != int(target_vocab):
        report_finding(
            "degenerate-paging", location,
            f"speculative drafter vocabulary ({drafter_vocab}) differs "
            f"from the target's ({target_vocab}): draft proposals "
            "index a different token space, so exact-acceptance "
            "verification rejects every round and speculative decoding "
            "only adds dispatches.",
            "use a drafter trained on the same tokenizer/vocabulary as "
            "the target model, or detach the drafter "
            "(Server(drafter=None))",
            dedupe=(location, "drafter-vocab"),
            target_vocab=int(target_vocab),
            drafter_vocab=int(drafter_vocab))


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def snapshot():
    """All findings (graph + concurrency) as plain data — what dump()
    writes and tools/check_graph.py renders."""
    by_rule = {}
    all_f = findings() + thread_findings()
    for f in all_f:
        by_rule[f["rule"]] = by_rule.get(f["rule"], 0) + 1
    return {
        "mode": _config.get("check"),
        "tsan": _locklint.armed(),
        "counts": by_rule,
        "findings": all_f,
        "lock_graph_edges": len(_locklint.lock_graph()),
    }


def _default_dump_path():
    d = _config.get("check_dir")
    if not d:
        return None
    return os.path.join(d, str(_diagnostics._rank()), "check.json")


def dump(path=None):
    """Write snapshot() as JSON to `path` (default:
    check_dir/<rank>/check.json — what tools/check_graph.py reads).
    Returns the path, or None when there is no target."""
    path = path or _default_dump_path()
    if not path:
        return None
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snapshot(), f, default=str)
    os.replace(tmp, path)   # readers never see a torn file
    return path


def _maybe_dump():
    """Refresh the check_dir dump after a new finding (findings are rare;
    failures are swallowed — analysis must never kill the step)."""
    if not _config.get("check_dir"):
        return
    try:
        dump()
    except OSError:
        pass


@atexit.register
def _dump_at_exit():
    if not _enabled or not _config.get("check_dir"):
        return
    try:
        dump()
    except OSError:
        pass


if _config.get("check") != "off":
    enable()
