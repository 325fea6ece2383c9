"""mx.inspect — compiled-executable cost attribution.

`mx.telemetry` (PR 1) says how fast a run is and `mx.diagnostics` (PR 2)
says why it died; neither says whether the achieved throughput is *good*.
This module closes that gap the way XLA-era tooling does: at every jit
compile (the same cache-miss sites `gluon/block.py` and
`parallel/trainer.py` already record into the flight ring), the lowered
computation is compiled once more ANALYTICALLY — `compiled.cost_analysis()`
and `compiled.memory_analysis()` — and a per-executable `CostRecord` lands
in a registry keyed by the jit-cache signature:

  * **flops / bytes accessed** — XLA's own cost model for the whole fused
    program (the per-kernel numbers TVM-style cost models are built from);
  * **device memory** — argument / output / temp / donated bytes and the
    derived execution-time peak, knowable BEFORE the step OOMs ("Memory
    Safe Computations with XLA", PAPERS.md);
  * **MFU** — achieved FLOP/s (flops / measured step time) against a
    per-backend peak-FLOPs table (TPU generations, bf16 peaks; override
    with the `peak_flops` knob — unknown backends report null, never 0/inf);
  * **roofline** — arithmetic intensity (flops / bytes accessed) against
    the backend's peak-FLOPs/HBM-bandwidth ridge point: compute-bound vs
    memory-bound;
  * **collective traffic** — estimated bytes per psum / all-gather /
    reduce-scatter per step, computed from the sharding specs
    (`parallel/specs.py`) + mesh shape with ring-algorithm costs, giving a
    compute-vs-comm budget per executable.

Surfaced everywhere the run is already visible: telemetry gauges/counters
(`executable_flops`, `executable_peak_bytes`, `mfu_ratio`,
`collective_bytes_est{op=...}`) and `cost` events, the flight-recorder
ring + post-mortem JSON (an OOM post-mortem names the executable with the
largest `peak_bytes`), `bench.py` fields (`mfu`, `achieved_tflops`,
`peak_device_bytes`, `comm_bytes_per_step`), the "Cost & efficiency"
section of `tools/telemetry_report.py`, and the `tools/inspect_report.py`
CLI over `inspect_dir` dumps.

Cost model: DISABLED (the default) is the production fast path — every
hook site checks one module-level bool and falls through; no analysis
compile, no allocation (`ci/run.sh sanity` asserts it). ENABLED costs one
extra lower+compile per jit-cache miss (served warm from the persistent
XLA compile cache) and a per-step fence in the
trainers so recorded step time is device time. Backends that return
partial or no cost analysis (CPU reports flops but little else) degrade
to null fields, never a crash.
"""
from __future__ import annotations

import atexit
import json
import os
import time

from . import _locklint
from . import config
from . import diagnostics as _diagnostics
from . import telemetry as _telemetry

__all__ = [
    "enable", "disable", "enabled", "reset",
    "CostRecord", "analyze_jit", "record_compiled", "note_step",
    "records", "get", "snapshot", "summary", "dump", "memory_breakdown",
    "peak_flops_per_chip", "peak_bandwidth_per_chip",
    "estimate_collectives", "key_repr",
]

_lock = _locklint.make_rlock("inspect.registry")
_enabled = False                  # the fast-path bool; see enable()/disable()
# plain dict when tsan-lite is off; armed, every mutation asserts _lock
# is held (the shared-structure half of the mx.check concurrency sweep)
_registry = _locklint.guarded_dict(_lock, "inspect.registry")
# (name, key) -> CostRecord
_last_live_dump = 0.0
_LIVE_DUMP_INTERVAL = 30.0        # seconds between inspect_dir refreshes

# Per-chip bf16 peak FLOP/s and HBM bandwidth by TPU generation (matched
# against device_kind substrings, most specific first). Published nominal
# numbers; the `peak_flops` knob overrides when the workload is not bf16
# or the table is stale for a new generation.
_PEAK_FLOPS_TABLE = (
    ("v6", 918e12), ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5", 459e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)
_PEAK_BW_TABLE = (
    ("v6", 1640e9), ("v5p", 2765e9), ("v5 lite", 819e9), ("v5e", 819e9),
    ("v5", 2765e9), ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
)

# memory-bound remediation hints: the applicable mx.kernels entry by
# executable-name fragment, most specific first (mirrors how mx.check's
# degenerate-sharding rule names mx.zero — a verdict should carry the
# fix that exists in-tree, not just the diagnosis). Surfaced in
# as_dict()/tools/inspect_report.py whenever roofline says memory-bound.
_KERNEL_HINTS = (
    ("moe", "pallas_ops.moe_kernels (kernels=auto): fused MoE "
            "dispatch/combine without the (N,E,C) one-hot tensor"),
    ("decode", "pallas_ops.int8_matmul via "
               "contrib.quantization.quantize_block (kernels=auto): "
               "int8 decode matmuls with the per-channel rescale fused"),
    ("serve", "pallas_ops.int8_matmul via "
              "contrib.quantization.quantize_block (kernels=auto): "
              "int8 decode matmuls with the per-channel rescale fused"),
    ("generate", "pallas_ops.int8_matmul via "
                 "contrib.quantization.quantize_block (kernels=auto): "
                 "int8 decode matmuls with the per-channel rescale "
                 "fused"),
    ("step", "pallas_ops.fused_update (kernels=auto): one-VMEM-pass "
             "optimizer update instead of the elementwise HLO chain"),
    ("train", "pallas_ops.fused_update (kernels=auto): one-VMEM-pass "
              "optimizer update instead of the elementwise HLO chain"),
)
_KERNEL_HINT_DEFAULT = (
    "mx.kernels (pallas_ops/): a hand-scheduled Pallas kernel can beat "
    "the generic lowering where the roofline says memory-bound — see "
    "README 'Kernel library'")

# telemetry series (get-or-create; updates are no-ops while telemetry is
# disabled, so inspect-without-telemetry costs nothing here)
_M_EXEC_FLOPS = _telemetry.gauge(
    "executable_flops", "XLA cost-model flops of one compiled executable "
    "(labeled by executable name)")
_M_EXEC_PEAK = _telemetry.gauge(
    "executable_peak_bytes", "estimated peak device bytes resident while "
    "one compiled executable runs (arguments + outputs + temps - donated)")
_M_MFU = _telemetry.gauge(
    "mfu_ratio", "achieved FLOP/s over per-chip peak for one executable "
    "(null-backed: stays unset when peak flops is unknown)")
_M_COLL_EST = _telemetry.counter(
    "collective_bytes_est", "estimated collective payload bytes per "
    "executed step, from sharding specs + mesh shape (ring-algorithm "
    "per-device cost), labeled by collective op")


def enabled():
    """True when cost attribution is on (hook sites read the module global
    `_enabled` directly — this accessor is the public spelling)."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def reset():
    """Drop every CostRecord (tests and run boundaries; the cached
    device-kind lookup drops too, for tests that swap backends)."""
    global _kind_cache
    with _lock:
        _registry.clear()
        _kind_cache = None


# ---------------------------------------------------------------------------
# backend peaks
# ---------------------------------------------------------------------------

_kind_cache = None                # device_kind can't change mid-process


def _device_kind():
    """device_kind of the first local device, '' when no backend is
    initialized yet (never cold-inits a backend — same rule as the
    diagnostics memory poll). Cached after the first successful lookup:
    note_step's mfu gauge would otherwise hit jax.local_devices() on
    every fenced step."""
    global _kind_cache
    if _kind_cache is not None:
        return _kind_cache
    devs = _diagnostics._jax_devices_if_initialized()
    if not devs:
        return ""
    _kind_cache = str(getattr(devs[0], "device_kind", ""))
    return _kind_cache


def _table_lookup(table, kind):
    kind = kind.lower()
    for frag, value in table:
        if frag in kind:
            return value
    return None


def peak_flops_per_chip():
    """Per-chip peak FLOP/s: the `peak_flops` knob when set, else the TPU
    generation table by device_kind, else None (CPU and unknown backends:
    MFU is then reported null)."""
    knob = float(config.get("peak_flops"))
    if knob > 0:
        return knob
    return _table_lookup(_PEAK_FLOPS_TABLE, _device_kind())


def peak_bandwidth_per_chip():
    """Per-chip HBM bandwidth (bytes/s) from the generation table, None
    when unknown — the roofline ridge point needs both peaks."""
    return _table_lookup(_PEAK_BW_TABLE, _device_kind())


# ---------------------------------------------------------------------------
# collective-traffic estimate
# ---------------------------------------------------------------------------

def estimate_collectives(mesh, sized_shardings, zero=None):
    """Estimated collective payload bytes per train step for one
    executable, from its parameter shardings + mesh shape.

    `sized_shardings`: [(nbytes, sharding_or_spec), ...] for every trained
    parameter. Ring-algorithm per-device costs: all-reduce moves
    2*(n-1)/n of the payload, all-gather and reduce-scatter (n-1)/n.
    Model: replicated params all-reduce (psum) their gradient over the
    data axes; fsdp-sharded params all-gather before use and
    reduce-scatter the gradient over fsdp, then all-reduce the shard over
    dp. `zero`: optional per-entry bools — a mx.zero'd parameter's
    would-be gradient psum is replaced by the reduce-scatter(grad) +
    all-gather(updated param) pair the zero step actually runs: the SAME
    ring bytes ((n-1)/n each way vs 2*(n-1)/n), attributed to the real
    ops. Tensor-parallel activation collectives are not modeled — this is
    the data-parallel budget, labeled an estimate everywhere it surfaces.
    Returns {} when no data axis spans more than one device."""
    dp = int(mesh.shape.get("dp", 1))
    fsdp = int(mesh.shape.get("fsdp", 1))
    n = dp * fsdp
    if n <= 1:
        return {}
    out = {"psum": 0.0, "all_gather": 0.0, "reduce_scatter": 0.0}

    def _reduce(nbytes, degree, zeroed):
        # one gradient reduction over `degree` devices: psum classically,
        # the rs/ag split (half the 2(n-1)/n each) when zero'd
        cost = 2.0 * (degree - 1) / degree * nbytes
        if zeroed:
            out["reduce_scatter"] += cost / 2.0
            out["all_gather"] += cost / 2.0
        else:
            out["psum"] += cost

    for i, (nbytes, sharding) in enumerate(sized_shardings):
        nbytes = float(nbytes)
        zeroed = bool(zero[i]) if zero else False
        spec = getattr(sharding, "spec", sharding)
        axes = set()
        for entry in (spec or ()):
            if entry is None:
                continue
            axes.update(entry if isinstance(entry, tuple) else (entry,))
        if "fsdp" in axes and fsdp > 1:
            out["all_gather"] += (fsdp - 1) / fsdp * nbytes
            out["reduce_scatter"] += (fsdp - 1) / fsdp * nbytes
            if dp > 1:
                _reduce(nbytes / fsdp, dp, zeroed)
        else:
            _reduce(nbytes, n, zeroed)
    return {k: int(v) for k, v in out.items() if v > 0}


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

def key_repr(key):
    """Stable string form of a jit-cache key (the registry key component).
    repr() is deterministic for the shape/dtype/flag tuples the caches
    use; anything unhashable upstream never reaches a cache anyway."""
    return repr(key)


class CostRecord:
    """Cost attribution for ONE compiled executable: XLA cost/memory
    analysis captured at compile time plus step-time accounting fed from
    the trainer. All analysis fields are None when the backend did not
    report them."""

    def __init__(self, name, key):
        self.name = name
        self.key = key
        self.created = time.time()
        self.compiles = 0
        self.flops = None             # XLA cost-model flops per execution
        self.bytes_accessed = None    # HBM bytes touched per execution
        self.argument_bytes = None
        self.output_bytes = None
        self.temp_bytes = None
        self.donated_bytes = None     # alias/donation savings
        self.peak_bytes = None        # args + outputs + temps - donated
        self.generated_code_bytes = None
        self.collectives = {}         # op -> estimated bytes per step
        self.steps = 0
        self.step_time_s = 0.0
        self.analysis_error = None    # str when cost/memory analysis failed

    # -- derived metrics ------------------------------------------------
    def avg_step_s(self):
        return self.step_time_s / self.steps if self.steps else None

    def achieved_flops(self):
        """Achieved FLOP/s over measured step time (None until both the
        cost analysis and at least one timed step exist)."""
        avg = self.avg_step_s()
        if self.flops is None or not avg:
            return None
        return self.flops / avg

    def mfu(self, peak=None):
        """Achieved/peak FLOP/s; None (never 0 or inf) when either the
        achieved rate or the per-chip peak is unknown."""
        ach = self.achieved_flops()
        peak = peak if peak is not None else peak_flops_per_chip()
        if ach is None or not peak:
            return None
        return ach / peak

    def arithmetic_intensity(self):
        """flops per byte accessed (the roofline x-axis)."""
        if not self.flops or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def roofline(self, peak=None, bandwidth=None):
        """'compute-bound' or 'memory-bound' against the backend ridge
        point (peak flops / HBM bandwidth); None when any input is
        unknown."""
        ai = self.arithmetic_intensity()
        peak = peak if peak is not None else peak_flops_per_chip()
        bandwidth = bandwidth if bandwidth is not None \
            else peak_bandwidth_per_chip()
        if ai is None or not peak or not bandwidth:
            return None
        return "compute-bound" if ai >= peak / bandwidth else "memory-bound"

    def comm_bytes_per_step(self):
        return sum(self.collectives.values()) if self.collectives else None

    def kernel_hint(self):
        """The mx.kernels remediation for a memory-bound executable:
        which pallas_ops kernel applies, matched on the executable name
        (None unless the roofline verdict is memory-bound)."""
        if self.roofline() != "memory-bound":
            return None
        name = (self.name or "").lower()
        for frag, hint in _KERNEL_HINTS:
            if frag in name:
                return hint
        return _KERNEL_HINT_DEFAULT

    def as_dict(self):
        d = {
            "name": self.name, "key": self.key, "created": self.created,
            "compiles": self.compiles, "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "donated_bytes": self.donated_bytes,
            "peak_bytes": self.peak_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "collectives": dict(self.collectives),
            "comm_bytes_per_step": self.comm_bytes_per_step(),
            "steps": self.steps,
            "step_time_s": round(self.step_time_s, 6),
            "avg_step_s": self.avg_step_s(),
            "achieved_flops": self.achieved_flops(),
            "mfu": self.mfu(),
            "arithmetic_intensity": self.arithmetic_intensity(),
            "roofline": self.roofline(),
            "kernel_hint": self.kernel_hint(),
        }
        if self.analysis_error:
            d["analysis_error"] = self.analysis_error
        return d


def _get_record(name, key):
    with _lock:
        rec = _registry.get((name, key))
        if rec is None:
            rec = CostRecord(name, key)
            _registry[(name, key)] = rec
        return rec


def memory_breakdown(mem):
    """(argument, output, temp, alias, peak) bytes from one
    CompiledMemoryStats — peak is the derived execution-time resident
    estimate (args + outputs + temps - donated), None when any component
    is missing. Shared with mx.memsafe so the pre-flight budget check and
    this registry can never account differently."""
    if mem is None:
        return None, None, None, None, None
    arg = getattr(mem, "argument_size_in_bytes", None)
    out = getattr(mem, "output_size_in_bytes", None)
    tmp = getattr(mem, "temp_size_in_bytes", None)
    alias = getattr(mem, "alias_size_in_bytes", None)
    peak = None
    if None not in (arg, out, tmp):
        peak = arg + out + tmp - (alias or 0)
    return arg, out, tmp, alias, peak


def _first_dict(analysis):
    """cost_analysis() returns a dict on newer jax, a list of per-module
    dicts on older; normalize to the entry computation's dict ({} when
    absent or unrecognizable)."""
    if isinstance(analysis, dict):
        return analysis
    if isinstance(analysis, (list, tuple)) and analysis \
            and isinstance(analysis[0], dict):
        return analysis[0]
    return {}


def record_compiled(name, key, compiled, collectives=None):
    """Attribute one compiled executable: run cost_analysis() /
    memory_analysis() defensively (partial or raising backends degrade to
    null fields) and fold the result into the registry, the telemetry
    gauges + `cost` event, and the diagnostics flight ring. Returns the
    CostRecord. Never raises."""
    rec = _get_record(name, key)
    errors = []
    cost = {}
    try:
        cost = _first_dict(compiled.cost_analysis())
    except Exception as e:
        errors.append(f"cost_analysis: {type(e).__name__}: {e}")
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception as e:
        errors.append(f"memory_analysis: {type(e).__name__}: {e}")
    with _lock:
        rec.compiles += 1
        if "flops" in cost:
            rec.flops = float(cost["flops"])
        if "bytes accessed" in cost:
            rec.bytes_accessed = float(cost["bytes accessed"])
        if mem is not None:
            arg, out, tmp, alias, peak = memory_breakdown(mem)
            rec.argument_bytes = arg
            rec.output_bytes = out
            rec.temp_bytes = tmp
            rec.donated_bytes = alias
            rec.generated_code_bytes = getattr(
                mem, "generated_code_size_in_bytes", None)
            if peak is not None:
                rec.peak_bytes = peak
        if collectives:
            rec.collectives = dict(collectives)
        if errors:
            rec.analysis_error = "; ".join(errors)
    if _telemetry._enabled:
        if rec.flops is not None:
            _M_EXEC_FLOPS.labels(executable=name).set(rec.flops)
        if rec.peak_bytes is not None:
            _M_EXEC_PEAK.labels(executable=name).set(rec.peak_bytes)
        _telemetry.event(
            "cost", executable=name, key=key, flops=rec.flops,
            bytes_accessed=rec.bytes_accessed, peak_bytes=rec.peak_bytes,
            argument_bytes=rec.argument_bytes,
            output_bytes=rec.output_bytes, temp_bytes=rec.temp_bytes,
            donated_bytes=rec.donated_bytes,
            collectives=dict(rec.collectives),
            peak_flops=peak_flops_per_chip(),
            peak_bandwidth=peak_bandwidth_per_chip(),
            backend=_device_kind() or None)
    if _diagnostics._enabled:
        # the ring entry makes shape-churn-into-OOM diagnosable: a
        # post-mortem whose last compiles show growing peak_bytes is the
        # smoking gun
        _diagnostics.record_event(
            "cost", executable=name, flops=rec.flops,
            peak_bytes=rec.peak_bytes, bytes_accessed=rec.bytes_accessed)
    return rec


def analyze_jit(name, key, jitted, *args, collectives=None):
    """Lower + compile `jitted` at `args`' signature purely for analysis
    and record the result (the execution path keeps its own lazily
    compiled executable — the second compile deserializes from the
    persistent cache instead of rebuilding).
    Returns the CostRecord, or one with an analysis_error when the
    backend cannot lower/compile out-of-line. Never raises."""
    if not _enabled:
        return None
    try:
        compiled = jitted.lower(*args).compile()
    except Exception as e:
        rec = _get_record(name, key)
        with _lock:
            rec.compiles += 1
            rec.analysis_error = f"lower/compile: {type(e).__name__}: {e}"
            if collectives:
                rec.collectives = dict(collectives)
        return rec
    return record_compiled(name, key, compiled, collectives=collectives)


def note_step(name, key, dur_s):
    """Fold one measured step execution into the executable's record:
    step count + wall time (the MFU denominator), the mfu_ratio gauge,
    and the per-op collective_bytes_est counters. Hook sites guard on
    `_enabled` themselves; this re-checks for direct callers."""
    if not _enabled:
        return
    with _lock:
        rec = _registry.get((name, key))
        if rec is None:
            return
        rec.steps += 1
        rec.step_time_s += float(dur_s)
    if _telemetry._enabled:
        m = rec.mfu()
        if m is not None:
            _M_MFU.labels(executable=name).set(m)
        for op, nbytes in rec.collectives.items():
            _M_COLL_EST.labels(op=op).inc(nbytes)
    _maybe_live_dump()


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def records():
    """All CostRecords, insertion-ordered."""
    with _lock:
        return list(_registry.values())


def get(name, key=None):
    """The CostRecord for `name` (+ `key` when several signatures exist);
    None when absent."""
    with _lock:
        if key is not None:
            return _registry.get((name, key))
        for (n, _), rec in _registry.items():
            if n == name:
                return rec
    return None


def snapshot():
    """The registry as plain data (what dump() writes and the post-mortem
    embeds): backend + peaks, every record, and the executable with the
    largest peak_bytes — the first thing to read after an OOM."""
    with _lock:
        recs = [r.as_dict() for r in _registry.values()]
    largest = None
    best = -1
    for r in recs:
        if r["peak_bytes"] is not None and r["peak_bytes"] > best:
            best, largest = r["peak_bytes"], r["name"]
    return {
        "backend": _device_kind() or None,
        "peak_flops_per_chip": peak_flops_per_chip(),
        "peak_bandwidth_per_chip": peak_bandwidth_per_chip(),
        "largest_peak_bytes_executable": largest,
        "records": recs,
    }


def summary():
    """Headline efficiency numbers for the hottest executable (most flops
    among those with timed steps, else most flops overall): the dict
    bench.py folds into its JSON line. All values nullable; {} when the
    registry is empty."""
    with _lock:
        recs = list(_registry.values())
    timed = [r for r in recs if r.steps and r.flops is not None] or \
        [r for r in recs if r.flops is not None] or recs
    if not timed:
        return {}
    rec = max(timed, key=lambda r: r.flops or 0.0)
    ach = rec.achieved_flops()
    return {
        "executable": rec.name,
        "flops": rec.flops,
        "mfu": rec.mfu(),
        "achieved_tflops": ach / 1e12 if ach is not None else None,
        "peak_device_bytes": rec.peak_bytes,
        "comm_bytes_per_step": rec.comm_bytes_per_step(),
        "arithmetic_intensity": rec.arithmetic_intensity(),
        "roofline": rec.roofline(),
    }


def _default_dump_path():
    d = config.get("inspect_dir")
    if not d:
        return None
    return os.path.join(d, str(_diagnostics._rank()), "inspect.json")


def dump(path=None):
    """Write snapshot() as JSON to `path` (default:
    inspect_dir/<rank>/inspect.json — the file tools/inspect_report.py
    reads). Returns the path, or None when there is no target."""
    path = path or _default_dump_path()
    if not path:
        return None
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snapshot(), f, default=str)
    os.replace(tmp, path)  # readers (live report) never see a torn file
    return path


def _maybe_live_dump():
    """Periodic inspect_dir refresh so the report CLI can watch a live
    run; rate-limited, and any write failure is swallowed (attribution
    must never kill the step it is observing)."""
    global _last_live_dump
    if not config.get("inspect_dir"):
        return
    now = time.monotonic()
    if now - _last_live_dump < _LIVE_DUMP_INTERVAL:
        return
    _last_live_dump = now
    try:
        dump()
    except OSError:
        pass


@atexit.register
def _dump_at_exit():
    if not _enabled or not config.get("inspect_dir"):
        return
    try:
        dump()
    except OSError:
        pass    # nothing useful to do with a write error at interpreter exit


if config.get("inspect"):
    enable()
