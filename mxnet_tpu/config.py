"""Typed runtime configuration (SURVEY §5.6).

The reference scatters behavior knobs across ~100 `MXNET_*` environment
variables read ad-hoc through `dmlc::GetEnv` (upstream
`docs/faq/env_var.md`); parameter structs are declared with
`dmlc::Parameter` (`3rdparty/dmlc-core/include/dmlc/parameter.h`). This
module is the TPU-native consolidation of both roles: every knob is
DECLARED once with a type, default, env var, and docstring; reads are
typed and validated; `describe()` enumerates the whole surface.

Precedence: programmatic `set()` > environment variable > declared default.
Call sites read through `config.get()` at use time, so `set()` takes
effect without process restart (module-import-time env snapshots are the
bug class this replaces).
"""
from __future__ import annotations

import os

from . import _locklint

__all__ = ["register_option", "get", "set", "reset", "describe", "option"]

_lock = _locklint.make_lock("config.registry")
_options = {}
_overrides = {}


class _Option:
    __slots__ = ("name", "default", "typ", "env", "doc", "choices")

    def __init__(self, name, default, typ, env, doc, choices):
        self.name = name
        self.default = default
        self.typ = typ
        self.env = env
        self.doc = doc
        self.choices = choices


def _coerce(opt, raw):
    if opt.typ is bool:
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    val = opt.typ(raw)
    if opt.choices and val not in opt.choices:
        raise ValueError(
            f"config '{opt.name}' must be one of {opt.choices}, got {val!r}")
    return val


def register_option(name, default, doc, typ=None, env=None, choices=None):
    """Declare a knob. env defaults to MXNET_TPU_<NAME>."""
    typ = typ or (type(default) if default is not None else str)
    env = env or ("MXNET_TPU_" + name.upper())
    with _lock:
        if name in _options:
            raise ValueError(f"config option '{name}' already registered")
        _options[name] = _Option(name, default, typ, env, doc, choices)
    return name


def get(name):
    opt = _options[name]
    with _lock:
        if name in _overrides:
            return _overrides[name]
    raw = os.environ.get(opt.env)
    if raw is None:
        return opt.default
    return _coerce(opt, raw)


def set(name, value):  # noqa: A001 - mirrors mx.config.set
    opt = _options[name]
    with _lock:
        _overrides[name] = _coerce(opt, value)


def reset(name=None):
    with _lock:
        if name is None:
            _overrides.clear()
        else:
            _overrides.pop(name, None)


def describe():
    """All options with their current value and provenance."""
    out = {}
    for name, opt in sorted(_options.items()):
        source = ("set" if name in _overrides
                  else "env" if os.environ.get(opt.env) is not None
                  else "default")
        out[name] = {"value": get(name), "default": opt.default,
                     "env": opt.env, "doc": opt.doc, "source": source}
    return out


def option(name):
    """The declaration record (for tooling/tests)."""
    return _options[name]


# ---------------------------------------------------------------------------
# framework knobs (each call site reads through get() at use time)
# ---------------------------------------------------------------------------
register_option(
    "fsdp_min_size", 1024,
    "Smallest parameter (elements) sharded over the fsdp axis; smaller ones "
    "stay replicated (reference: MXNET_KVSTORE_BIGARRAY_BOUND).")
register_option(
    "fused_lamb", True,
    "Use the fused multi-tensor LAMB path (flat f32 master weights) when "
    "params are replicated.")
register_option(
    "lamb_moments_dtype", "float32", choices=("float32", "bfloat16"),
    doc="Storage dtype for fused-LAMB moment buffers. 'bfloat16' cuts "
        "optimizer HBM traffic ~30% at BERT scale (the apply pass is "
        "bandwidth-bound); math stays f32, storage rounds through bf16. "
        "Second-moment rounding slightly coarsens adaptive scaling — "
        "validated on the convergence gates, off by default.")
register_option(
    "prng", "auto", choices=("auto", "rbg", "threefry2x32"),
    doc="PRNG implementation: 'rbg' (TPU hardware generator, fast), "
        "'threefry2x32' (counter-exact), or 'auto' (rbg on TPU).")
register_option(
    "dataloader_timeout", 300.0,
    "Seconds the process-worker DataLoader waits with no batch arriving "
    "before declaring the workers deadlocked (a jax/XLA call inside a "
    "forked worker). 0 disables the watchdog.")
register_option(
    "kernels", "auto", choices=("off", "auto", "on"),
    doc="mx.kernels Pallas library gate (pallas_ops/: flash "
        "attention, int8 serving matmul with fused per-channel "
        "rescale, fused optimizer updates, paged decode attention, "
        "fused MoE dispatch/combine). 'off': every call site runs its "
        "XLA-native fallback and nothing imports "
        "jax.experimental.pallas (the trainer hot loop stays "
        "pallas-free — asserted by ci/run.sh sanity). 'auto' "
        "(default): a kernel engages when it can win — a TPU backend "
        "(or MXNET_TPU_PALLAS_INTERPRET=1, the interpreter path "
        "tier-1 tests ride), shape eligibility, and for the "
        "fused-update and paged-attention kernels a single-device "
        "step (jit cannot partition a pallas_call; flash attention "
        "and the MoE kernels run inside shard_map and engage on any "
        "mesh). 'on' raises instead of silently falling back when "
        "Pallas cannot run. Decided at trace time: 'off' executables "
        "are byte-identical to a build without the library.")
register_option(
    "kernels_min_elements", 1 << 16,
    "Smallest buffer (elements) the fused optimizer-update kernels "
    "engage on; below it the XLA lowering is kept (kernel launch "
    "overhead beats one fused pass over tiny LayerNorm/bias state — "
    "same argument as fsdp_min_size / zero_min_size).")
register_option(
    "pallas_bwd_min_len", 512,
    "KV length at or above which flash-attention backward uses the "
    "blockwise Pallas kernels instead of XLA's fused LxL formulation "
    "(measured crossover at 512x512 blocks: Pallas 5.3ms vs hybrid 6.6ms "
    "at L=512 BERT-base shapes; dropout>0 always uses Pallas).")
register_option(
    "debug", False,
    "Debug mode: op-by-op execution (no jit) + NaN checks. Usually set via "
    "mxnet_tpu.debug() rather than this knob.")
register_option(
    "telemetry", False,
    "Enable the mx.telemetry metrics registry and event stream at import. "
    "Off by default: every instrumentation site then reduces to a single "
    "module-bool check (the guarded fast path asserted by ci/run.sh "
    "sanity). mx.telemetry.enable()/disable() toggle at runtime.")
register_option(
    "telemetry_jsonl_path", "",
    "When set, telemetry events are appended to this JSONL file every "
    "telemetry_flush_interval seconds and a final metrics snapshot line is "
    "written at process exit. Empty disables auto-flush; "
    "mx.telemetry.dump_jsonl(path) still works.")
register_option(
    "telemetry_flush_interval", 5.0,
    "Seconds between auto-flushes of buffered telemetry events to "
    "telemetry_jsonl_path. Checked on event emission (no flush thread).")
register_option(
    "diagnostics", False,
    "Arm mx.diagnostics at import: flight recorder, crash post-mortem "
    "writer (sys.excepthook + atexit + faulthandler), and — when "
    "watchdog_deadline_s > 0 — the hang watchdog. Off by default: every "
    "recording site then reduces to a single module-bool check and no "
    "ring buffer or watchdog thread exists (asserted by ci/run.sh "
    "sanity). mx.diagnostics.install() arms at runtime.")
register_option(
    "diagnostics_dir", "diagnostics",
    "Base directory for per-rank diagnostic artifacts: "
    "<dir>/<rank>/postmortem.json, worker.log (written by tools/"
    "launch.py), faulthandler.log, watchdog_stacks.txt. Merged across "
    "ranks by tools/postmortem_report.py.")
register_option(
    "diagnostics_ring_size", 256,
    "Flight-recorder capacity: the last N step/compile records kept in "
    "the in-memory ring buffer and written into postmortem.json.")
register_option(
    "watchdog_deadline_s", 0.0,
    "Seconds without a completed step before the mx.diagnostics watchdog "
    "fires (names the last-entered scope, dumps all-thread stacks and a "
    "post-mortem, then re-arms on the next step). 0 disables the "
    "watchdog thread entirely.")
register_option(
    "trainer_async_fence_every", 0,
    "Host-fence the trainers every N steps (block_until_ready on the "
    "step's loss / updated params) to bound how far dispatch runs ahead "
    "of the device. 0 (default) never fences on the hot path — the fence "
    "then only happens on an explicit .item()/asscalar() or when "
    "telemetry/nan_sentinel (which document that they fence) are "
    "enabled.")
register_option(
    "device_prefetch_depth", 2,
    "Batches mx.dataflow.prefetch_to_mesh stages onto the mesh ahead of "
    "the consumer (H2D transfer overlaps device compute). Also the depth "
    "the Estimator uses when fit() is handed a gluon DataLoader. 0 "
    "disables device-side prefetch in the estimator.")
register_option(
    "bucket_pad_min", 32,
    "Smallest bucket mx.dataflow.BucketPad rounds a varlen axis up to "
    "under the default power-of-two policy; explicit axis_buckets lists "
    "override it. Bounds the jit-cache population for varlen workloads "
    "(padding overhead is visible in the bucket_pad_waste_ratio "
    "histogram).")
register_option(
    "inspect", False,
    "Enable mx.inspect at import: every jit-cache miss additionally "
    "lowers+compiles the same computation for XLA cost_analysis() / "
    "memory_analysis() and keeps a per-executable CostRecord (flops, bytes "
    "accessed, device memory, estimated collective traffic, MFU). Off by "
    "default: every hook site then reduces to a single module-bool check "
    "and no analysis compile happens (asserted by ci/run.sh sanity). "
    "mx.inspect.enable()/disable() toggle at runtime. Trainers fence each "
    "step while enabled so recorded step time is device time.")
register_option(
    "inspect_dir", "",
    "When set, mx.inspect writes its registry to <dir>/<rank>/inspect.json "
    "at process exit and refreshes it periodically during the run (so "
    "tools/inspect_report.py can read a live job). Empty keeps the "
    "registry in-memory only; mx.inspect.dump(path) still works.")
register_option(
    "peak_flops", 0.0,
    "Per-chip peak FLOP/s used for MFU and roofline classification. 0 "
    "(default) auto-detects from the device kind (TPU generation table in "
    "mx.inspect; bf16 peaks); set explicitly for backends the table does "
    "not know (e.g. CPU) or for non-bf16 workloads. When neither yields a "
    "value, MFU is reported null, never 0 or inf.")
register_option(
    "resilience", False,
    "Arm mx.resilience at import: SIGTERM/SIGINT preemption handler "
    "(finish the in-flight step, write a final checkpoint, exit the "
    "distinct EXIT_PREEMPTED code), periodic verified checkpoints "
    "(checkpoint_dir / checkpoint_every_n_steps), auto-resume (resume "
    "knob), transient-fault retries, and the fault_inject harness. Off "
    "by default: the trainer hook reduces to a single module-bool check, "
    "no signal handlers are installed, and save/restore do no manifest "
    "hashing (asserted by ci/run.sh sanity). mx.resilience.install() "
    "arms at runtime.")
register_option(
    "checkpoint_dir", "",
    "Base directory for mx.resilience managed checkpoints "
    "(<dir>/step_<n>/ with an atomic-renamed manifest.json carrying "
    "per-file checksums + step + mesh fingerprint). Used by the "
    "ShardedTrainer periodic-checkpoint hook, the preemption final save, "
    "auto-resume, and Estimator.fit checkpointing. Empty disables "
    "managed checkpoints.")
register_option(
    "checkpoint_every_n_steps", 0,
    "Save a managed checkpoint every N completed ShardedTrainer steps "
    "(requires checkpoint_dir and mx.resilience enabled). 0 disables "
    "periodic saves — the preemption final save still fires.")
register_option(
    "checkpoint_keep", 3,
    "Managed checkpoints retained under checkpoint_dir (keep-last-N; "
    "older ones and stale *.tmp-* leftovers from killed saves are "
    "GC'd after each save, on process 0). <=0 keeps everything.")
register_option(
    "resume", "",
    "Auto-resume policy for a fresh ShardedTrainer / Estimator.fit while "
    "mx.resilience is enabled: 'auto' restores the newest checkpoint "
    "under checkpoint_dir that passes checksum+mesh verification "
    "(falling back past torn/corrupt ones), an explicit path restores "
    "that checkpoint, '' (default) starts fresh.")
register_option(
    "fault_inject", "",
    "mx.resilience fault-injection spec (comma-separated): "
    "'sigterm@step:5' (graceful-preemption path), 'kill@step:3' (rank "
    "death via SIGKILL), 'corrupt_ckpt@step:4' (flip bytes in that "
    "step's checkpoint after its manifest is written), 'stall_input:250' "
    "(one 250ms input-pipeline stall), 'exc@step:2' (crash), 'oom@step:3' "
    "(synthetic RESOURCE_EXHAUSTED at the dispatch of step 3, before any "
    "transfer/donation — drives the mx.memsafe oom_recover degradation "
    "ladder; repeat the spec to OOM the retry too), "
    "'shrink@step:3' / 'grow@step:3' (elastic reshape request: save a "
    "final checkpoint, exit EXIT_SHRINK=84 / EXIT_GROW=85 so a "
    "tools/launch.py --elastic supervisor relaunches the gang smaller by "
    "every rank that fired / one worker larger — use 'shrink@step:3"
    "@rank:N' to lose exactly one worker), 'hang@step:3' (the step "
    "boundary blocks and never returns — a stuck collective; drives the "
    "mx.guard heartbeat-staleness kill and the peers' collective "
    "deadline), 'corrupt_grad@step:4' (deterministic bit-flip in one "
    "replica of the first gradient/parameter leaf as the step-4 update "
    "lands — the SDC the mx.guard digest vote must catch and attribute), "
    "'stall_heartbeat:500' (suppress heartbeat file writes for 500 ms; "
    "the process stays healthy, only its liveness signal goes dark), "
    "'slow_client:200' (mx.serve: the request stream consumer stalls "
    "200 ms per token — scheduler throughput must not care), "
    "'burst:8@step:3' (mx.serve: the server fires its on_burst hook "
    "with 8 at scheduler step 3 — a deterministic load spike), "
    "'cancel@req:2' (mx.serve: cancel request id 2 at the next "
    "scheduler step — the mid-generation cancellation drill). "
    "Append '@rank:N' to target "
    "one rank, '@every_restart' to "
    "re-fire after a supervised relaunch. Empty (default) injects "
    "nothing.")
register_option(
    "reshard", "auto", choices=("auto", "off", "host"),
    doc="Cross-topology checkpoint redistribution policy "
        "(parallel/reshard.py). 'auto' (default): a verified checkpoint "
        "whose mesh/param-mode fingerprint differs from the restoring "
        "trainer is redistributed onto the current topology via a planned "
        "reshard (params, optimizer state, RNG and step counter stay "
        "bit-exact; peak memory bounded by the largest single array). "
        "'host' forces the host-side gather/scatter path for live "
        "resizes (degenerate topologies where no collective can run). "
        "'off' restores the strict behavior: a mesh mismatch raises "
        "MeshMismatchError naming both fingerprints.")
register_option(
    "reshard_chunk_bytes", 64 * 1024 * 1024,
    "Live-resize arrays larger than this take the host gather/scatter "
    "path when their move would need a device-side gathered intermediate "
    "(merge / axis-flip redistributions); smaller ones ride the planned "
    "device collective. Bounds per-device transient memory during "
    "elastic.resize_trainer.")
register_option(
    "elastic", False,
    "Elastic gang default for tools/launch.py (read from the env var at "
    "launcher startup — the launcher stays jax-free): on a rank death or "
    "shrink/grow request, relaunch the gang at the SURVIVING world size "
    "(floored at min_workers) instead of the original shape; workers "
    "resuming with reshard='auto' then redistribute the checkpoint onto "
    "the new topology. Equivalent to the --elastic flag.")
register_option(
    "min_workers", 1,
    "Smallest world size an elastic tools/launch.py gang may shrink to "
    "(read from the env var at launcher startup): a relaunch after slot "
    "losses is clamped to this floor, never below it. Equivalent to the "
    "--min-workers flag.")
register_option(
    "retry_max_attempts", 3,
    "Total tries mx.resilience.RetryPolicy makes on a retryable "
    "transient fault (prefetch staging, DataLoader worker respawn, "
    "checkpoint I/O). 1 disables retries.")
register_option(
    "retry_backoff_s", 0.5,
    "Base backoff before the first RetryPolicy retry; doubles per "
    "attempt (exponential), jittered +-25%.")
register_option(
    "retry_max_backoff_s", 30.0,
    "Upper bound on a single RetryPolicy backoff sleep, whatever the "
    "attempt count.")
register_option(
    "device_bytes_limit", 0,
    "Device memory capacity (bytes) the mx.memsafe pre-flight budget check "
    "and dataflow.autofit compare predicted peaks against. 0 (default) "
    "auto-detects from device.memory_stats()['bytes_limit'] (absent on "
    "CPU); a positive value overrides — CPU CI and tests simulate any "
    "capacity this way. Setting it arms memsafe at trainer construction.")
register_option(
    "memory_headroom_warn", 0.1,
    "Fraction of device capacity below which the mx.memsafe pre-flight "
    "check emits a memory-headroom warning (event + stderr, once per "
    "executable) alongside the memory_headroom_bytes gauge. 0 disables "
    "the warning (the hard budget check still raises on a predicted "
    "overrun).")
register_option(
    "remat_policy", "", choices=("", "none", "dots_saveable", "layers",
                                 "full"),
    doc="Default rematerialization policy applied to every block "
        "(mx.memsafe graduated remat; HybridBlock.remat(policy=...) "
        "overrides per block). In increasing memory savings / recompute "
        "cost: 'none' saves every intermediate; 'dots_saveable' "
        "jax.checkpoint keeping matmul outputs; 'layers' per-layer "
        "checkpointing (activation memory O(1) in depth — what the legacy "
        "per-model remat=True flag meant); 'full' additionally "
        "checkpoints the whole stack so only model inputs survive the "
        "forward pass. Empty (default) defers to per-block/per-model "
        "settings.")
register_option(
    "oom_recover", "off", choices=("off", "auto"),
    doc="Out-of-memory recovery at the trainer step boundary. 'off' "
        "(default) keeps fail-fast behavior and the zero-overhead hot "
        "path (one module bool, no handlers — asserted by ci/run.sh "
        "sanity). 'auto' catches RESOURCE_EXHAUSTED and pre-flight "
        "MemoryBudgetError and walks the degradation ladder: escalate the "
        "remat policy one rung, then shard the optimizer state across "
        "the data replicas (mx.zero — bit-identical values, (D-1)/D of "
        "the opt-state bytes back), then halve the batch via gradient-"
        "accumulation microbatching (loss/grad parity up to reduction "
        "order), re-plan, retry — each transition logged to telemetry, "
        "the flight ring, and the post-mortem 'memsafe' section.")
register_option(
    "zero", "off", choices=("off", "auto", "on"),
    doc="mx.zero cross-replica optimizer-state sharding "
        "(parallel/zero.py). 'off' (default) is the zero-overhead fast "
        "path: the ShardedTrainer makes no call into the zero module — "
        "no state planning, no sharding constraints (asserted by "
        "ci/run.sh sanity). 'auto' shards the optimizer state (SGD/Adam "
        "moments; the fused-LAMB fp32 flat master and moments) across "
        "the mesh's data axes at trainer construction whenever they "
        "span >1 device, replacing the step's gradient psum + "
        "replicated update with reduce-scatter -> per-shard update -> "
        "all-gather inside the same jitted step: resident opt-state "
        "bytes per device drop by (D-1)/D at data extent D, collective "
        "payload unchanged. 'on' insists — construction raises when "
        "nothing can shard. Independent of the knob, the "
        "oom_recover=auto ladder may enable sharding on a live trainer "
        "as the rung between remat=full and gradient accumulation.")
register_option(
    "zero_min_size", 1024,
    "Smallest parameter (elements) whose optimizer state mx.zero shards "
    "across the data axes; smaller state (LayerNorm/bias moments) stays "
    "with its parameter's sharding — the reshard churn would outweigh "
    "the bytes (same argument as fsdp_min_size).")
register_option(
    "check", "off", choices=("off", "warn", "error"),
    doc="mx.check static analysis mode. 'off' (default) is the "
        "zero-overhead fast path: the jit-cache-miss hook sites reduce to "
        "one module-bool check, no jaxpr walk, no findings registry "
        "(asserted by ci/run.sh sanity). 'warn' lints every freshly traced "
        "computation (large baked constants, donation misses, silent "
        "bf16->f32/f64 promotions, predictable retrace hazards, degenerate "
        "sharding) and reports findings to stderr + the "
        "check_findings_total{rule=...} telemetry counter + "
        "check_dir/<rank>/check.json. 'error' additionally raises "
        "CheckError on the first finding, naming the rule, location, and "
        "remediation — the CI 'static' stage runs the model zoo this way.")
register_option(
    "check_dir", "",
    "When set, mx.check writes its findings to <dir>/<rank>/check.json at "
    "process exit (and refreshes after each new finding) so "
    "tools/check_graph.py can merge and render a multi-rank report. Empty "
    "keeps findings in-memory only; mx.check.dump(path) still works.")
register_option(
    "check_large_const_bytes", 1 << 20,
    "mx.check graph-lint threshold: a constant baked into a traced "
    "computation (closure-captured numpy/jax array, not a parameter) at "
    "or above this many bytes fires the 'large-constant' rule — baked "
    "constants are re-staged per executable and defeat donation. "
    "<=0 disables the rule.")
register_option(
    "check_promotion_min_bytes", 1 << 20,
    "mx.check graph-lint threshold: a bf16/f16 -> f32/f64 "
    "convert_element_type whose OUTPUT is at or above this many bytes "
    "fires the 'dtype-promotion' rule (a non-weak f32 scalar — e.g. "
    "np.float32 — silently promotes whole activation tensors; python "
    "scalars stay weak and do not). Small deliberate upcasts like the "
    "per-sample loss stay under the threshold. <=0 disables the rule.")
register_option(
    "check_replicated_min_bytes", 64 << 20,
    "mx.check graph-lint threshold for the 'degenerate-sharding' rule: on "
    "a mesh whose data axes span >1 device, fully-replicated trained "
    "parameters (param_mode='replicate') or replicated batch inputs at or "
    "above this many bytes are flagged (every device holds the full "
    "array; remediation: fsdp param mode / mx.zero, or a sharded batch "
    "spec). <=0 disables the rule.")
register_option(
    "check_donation_min_bytes", 1 << 20,
    "mx.check graph-lint threshold for the 'donation-miss' rule: an input "
    "buffer at or above this many bytes whose shape+dtype exactly matches "
    "an output of the same executable (state threading — KV caches, "
    "optimizer moments) and is NOT donated double-buffers that state "
    "every call. <=0 disables the aval-matching detector (the "
    "trainer-level donate=False detector still fires).")
register_option(
    "check_retrace_limit", 4,
    "mx.check graph-lint: distinct values of ONE signature component "
    "(an input-shape axis, or a baked python scalar like a mutated "
    "learning rate) observed for the same block/trainer before the "
    "'retrace-hazard' rule fires — each distinct value is a full "
    "recompile, and the component is predicted to keep varying. "
    "<=0 disables the rule.")
register_option(
    "trace", "off", choices=("off", "on"),
    doc="mx.trace step tracing. 'off' (default) with no jax.profiler "
        "session recording is the fast path: Server.step, "
        "ShardedTrainer.step_async and the prefetcher read "
        "mx.trace.live() once a step and every span site tests that "
        "local — no span, no recorder call, no buffer (asserted by "
        "ci/run.sh sanity). The spans go live when this is 'on' OR a "
        "profiler session records (start jax.profiler and the program's "
        "spans are in the capture, on the device trace's clock): each "
        "enters a jax.profiler.TraceAnnotation and is appended to the "
        "span buffer, tagged (rank, step), for every "
        "trace_sample_every-th step. A live span never fences. 'on' "
        "additionally runs the step-skew probe and writes span files. "
        "tools/launch.py --trace-dir arms every worker; merge the "
        "per-rank files with tools/trace_report.py. docs/trace.md lists "
        "the span names.")
register_option(
    "trace_dir", "",
    "Base directory for mx.trace span files: each rank appends its "
    "sampled spans and skew probes to <dir>/<rank>/trace.jsonl (meta "
    "line first, carrying the rank's wall-clock epoch so "
    "tools/trace_report.py can align all ranks on one timeline). Empty "
    "keeps spans in-memory only (bounded buffer; mx.trace.flush(path) "
    "still works).")
register_option(
    "trace_sample_every", 1,
    "Record mx.trace spans for every N-th step (and every N-th record "
    "of step-less streams like the input batch-wait). 1 traces "
    "everything — right for short diagnostic windows; raise it for "
    "always-on production tracing so the span volume shrinks by N (a "
    "traced step is never fenced). Compile and checkpoint spans are "
    "always recorded (rare, seconds-scale).")
register_option(
    "trace_skew_every", 16,
    "Run the mx.trace step-skew probe every N SAMPLED steps: each rank "
    "wall-stamps its arrival at the collective boundary (an all-gather "
    "of timestamps when jax runs multi-process), feeding the "
    "step_skew_seconds / straggler_rank telemetry gauges, a flight-ring "
    "'trace' entry, and per-rank skew records tools/trace_report.py "
    "turns into measured cross-rank arrival spread. 0 disables the "
    "probe (spans still record).")
register_option(
    "check_threads", False, env="MXNET_TPU_CHECK_THREADS",
    doc="tsan-lite mode (read by mxnet_tpu/_locklint.py at import, also "
        "directly from the env var so the jax-free tools/launch.py sees "
        "it): instrumented-module locks become order-recording "
        "CheckedLocks — an acquisition that closes a cycle in the "
        "lock-order graph raises LockOrderError naming both acquisition "
        "stacks, and guarded shared structures assert their lock is held "
        "on mutation. Off (default): the factories return plain "
        "threading primitives, zero overhead. The CI 'static' stage runs "
        "the threaded unit tests under this mode.")
register_option(
    "guard", False,
    "Arm mx.guard at import: per-rank liveness heartbeats (written to "
    "diagnostics_dir/<rank>/heartbeat.json, polled by tools/launch.py "
    "--heartbeat-timeout, which kills stuck-but-alive workers so the "
    "elastic relaunch path takes over), the gang-aware collective "
    "deadline (collective_timeout_s), and the SDC digest vote "
    "(sdc_check_every). Off by default: every hook site then reduces to "
    "a single module-bool check — no heartbeat record, no deadline "
    "thread, no digest (asserted by ci/run.sh sanity). "
    "mx.guard.enable() arms at runtime.")
register_option(
    "heartbeat_timeout_s", 60.0,
    "Seconds without a fresh heartbeat before a rank is considered "
    "stuck: tools/launch.py --heartbeat-timeout (which exports this "
    "env to workers) SIGKILLs the stuck-but-alive process so the gang "
    "relaunches — with --elastic, at the surviving world size — instead "
    "of blocking in a collective forever. Also paces the heartbeat "
    "file-write interval (timeout/4, capped at 1 s). Size it above the "
    "worst-case checkpoint write: saves beat at start and end, but a "
    "single write longer than the timeout reads as a stall.")
register_option(
    "collective_timeout_s", 0.0,
    "mx.guard gang-aware deadline on the step fence/collective "
    "boundary: when no step completes within this many seconds (first "
    "step onward; compiles and checkpoint writes suspend the clock), "
    "the rank dumps a post-mortem naming the suspected dead peer "
    "(oldest peer heartbeat + last mx.trace skew straggler) and exits "
    "EXIT_PEER_LOST (86) so the supervisor relaunches the gang. 0 "
    "(default) disables the deadline thread entirely.")
register_option(
    "sdc_check_every", 0,
    "Run the mx.guard silent-data-corruption digest vote every N "
    "completed trainer steps: hash a deterministic per-replica digest "
    "of the post-all-reduce params (bit-identical across data-parallel "
    "replicas by construction), exchange gang-wide, majority-vote the "
    "corrupt rank, and roll the gang back to the last verified "
    "checkpoint (a twice-corrupt rank is quarantined via the elastic "
    "shrink path). Needs param_mode='replicate'. 0 (default) disables.")
register_option(
    "serve", False,
    "Arm mx.serve instrumentation at import: the shared decode dispatch "
    "site (models/_decode.jit_flat_step) counts dispatches for the "
    "serving scheduler. Off by default: the hook reduces to a single "
    "module-bool check — zero calls, zero allocations (asserted by "
    "ci/run.sh sanity). Constructing a serve.Server arms it regardless.")
register_option(
    "serve_slots", 4,
    "Decode batch slots per KV-cache bucket in the mx.serve continuous-"
    "batching scheduler: each active bucket runs one batched step over "
    "this many request slots (its caches are (slots, H, bucket, D)). "
    "More slots = more requests decoded per dispatch, more KV memory "
    "per bucket.")
register_option(
    "serve_queue_depth", 64,
    "Bound on the mx.serve admission queue. A submit beyond it triggers "
    "the serve_shed load-shedding policy instead of growing the queue "
    "without limit — the backpressure half of overload safety.")
register_option(
    "serve_shed", "reject", choices=("reject", "oldest"),
    doc="mx.serve load-shedding policy when the bounded queue is full: "
        "'reject' turns the NEW request away (503-style verdict, the "
        "client can back off), 'oldest' displaces the longest-waiting "
        "queued request in favor of the newcomer (freshness over "
        "fairness — right for requests whose answers go stale).")
register_option(
    "serve_deadline_ms", 0.0,
    "Default per-request deadline for mx.serve, in milliseconds from "
    "submit (per-request deadline_ms overrides). Expired requests are "
    "evicted between decode steps — mid-generation — and their KV pages "
    "reclaimed; requests that expire while still queued are dropped "
    "with the same 504-style verdict. 0 (default) sets no deadline.")
register_option(
    "serve_min_new_tokens", 1,
    "Floor for the mx.serve graceful-degradation shrink rung: under "
    "memory pressure a request's max_new_tokens may be clamped down to "
    "the largest KV bucket that fits, but never below this many new "
    "tokens — beyond that the ladder moves to evict-and-requeue, then "
    "rejection.")
register_option(
    "serve_buckets", "",
    "Comma-separated total-length (prompt + max_new_tokens) buckets for "
    "the mx.serve KV caches, e.g. '64,128,256'. Empty (default) uses "
    "power-of-two buckets floored at bucket_pad_min and capped at the "
    "model's max_length — either way a stream of novel request lengths "
    "compiles at most one step executable per bucket.")
register_option(
    "fleet", "off", choices=("off", "on"),
    doc="mx.fleet replicated serving. 'off' (default) is the zero-"
        "overhead fast path: no replica endpoint, no router, no fleet "
        "section in mx.scope statusz — every hook site reduces to one "
        "module-bool check (asserted by ci/run.sh fleet). 'on' (or "
        "constructing a fleet.ReplicaEndpoint / running "
        "`python -m mxnet_tpu.fleet`) arms the replica-side serving "
        "endpoint so a fleet Router can health-route, drain, fail "
        "over and roll this process. The router itself is stdlib-only "
        "and launched by `tools/launch.py --serve-replicas N`.")
register_option(
    "fleet_port", 8900,
    "Base port for mx.fleet: the router's front door listens here and "
    "replica R serves its generation endpoint on port+1+R (the same "
    "base+1+rank layout as scope_port, on a separate base so the two "
    "gangs of listeners never collide).")
register_option(
    "fleet_retry_max", 3,
    "Per-request failover budget in the mx.fleet router: a request "
    "whose replica dies mid-stream (or answers a retriable overload "
    "verdict) is re-submitted to a surviving replica at most this "
    "many times — with a `skip` high-water mark so tokens already "
    "delivered are never re-sent — before the router returns a 503.")
register_option(
    "fleet_health_interval_ms", 250.0,
    "mx.fleet router health-poll cadence: every interval the router "
    "fetches each replica's /healthz liveness and /statusz placement "
    "payload (queue depth, slot occupancy, TTFT percentiles, memsafe "
    "admission hints) with a hard per-fetch timeout, so routing "
    "decisions ride data no staler than one interval.")
register_option(
    "fleet_stall_timeout_ms", 10000.0,
    "mx.fleet router per-read stall bound on an in-flight generation "
    "stream: a replica that stops producing tokens for this long "
    "(wedged-but-alive — the wedge_replica drill) is treated as dead "
    "and the request fails over to a survivor. 0 disables.")
register_option(
    "fleet_drain_grace_s", 30.0,
    "Zero-drop drain budget: a SIGTERMed replica stops admitting, "
    "then finishes in-flight requests for up to this many seconds; "
    "whatever is still running at expiry is cancelled with a "
    "retriable verdict so the router requeues it on a survivor "
    "(replay skips already-streamed tokens). Then the process exits "
    "via the resilience preemption path (exit code 83).")
register_option(
    "fleet_autoscale", "off", choices=("off", "on"),
    doc="mx.fleet queue-wait autoscaling. 'on' grows the replica "
        "count when every healthy replica's published p99 queue wait "
        "stays above fleet_autoscale_p99_ms for a full "
        "fleet_autoscale_window_s, and shrinks when the fleet sits "
        "idle (zero queued, negligible queue wait) for the same "
        "window — clamped to [--min-workers, --serve-replicas-max] "
        "through the launcher's elastic world-size plumbing.")
register_option(
    "fleet_autoscale_p99_ms", 500.0,
    "Sustained p99 queue-wait threshold (milliseconds) above which "
    "the mx.fleet router asks the supervisor for one more replica; "
    "scale-down arms below one quarter of this value.")
register_option(
    "fleet_autoscale_window_s", 5.0,
    "How long the mx.fleet autoscale pressure signal must persist "
    "before a scale event fires — hysteresis so one burst or one "
    "idle poll cannot flap the replica count.")
register_option(
    "pages_page_size", 16,
    "Tokens per mx.pages KV page, the unit mx.serve's pool hands out "
    "and its prefix tree shares. Buckets round up to a page multiple "
    "(and the servable max_length rounds down to one), so a bucket's "
    "page table covers it exactly. "
    "Smaller pages share prefixes at finer grain but deepen the "
    "per-step page-table walk; keep it at or below the smallest "
    "bucket (mx.check 'degenerate-paging' flags the inversion).")
register_option(
    "pages_pool_pages", 0,
    "Data pages in the mx.pages pool (scratch pages for masked rows "
    "are added on top, one per slot), allocated once when a "
    "serve.Server is built. 0 (default) sizes the pool to "
    "slots * max_length/page_size: every slot at the longest servable "
    "length. Admission under an exhausted pool walks the degradation "
    "ladder: evict unreferenced prefix-tree leaves, shrink, "
    "evict-and-requeue, reject.")
register_option(
    "pages_prefill_chunk", 8,
    "Prompt tokens ONE request feeds in one mx.serve step (a per-"
    "request cap, not the width of a step). A step is one pass over its "
    "tokens: a token of every decoding request and up to this many "
    "prompt tokens of every request still inside its prompt, in "
    "admission order. The width of a pass is derived, not set: the "
    "narrowest rung that holds its tokens of a ladder `slots`, 2 x slots "
    "and doublings while a rung stays within 256 rows (where a pass stops "
    "being bound by its weights on a TPU v5e) and under slots x this — "
    "one executable a rung and bucket (32 slots: 32, 64, 128 for chunks "
    "of 8, and 256 for longer ones); a step with more tokens than the top "
    "rung takes further passes.")
register_option(
    "pages_spec_k", 4,
    "Draft tokens per speculative decoding round (a serve.Server "
    "with a drafter). The drafter chains k greedy proposals, the target "
    "verifies all of them plus the bonus token in one pass of k+1 "
    "rows a slot, and exact acceptance keeps the longest agreeing "
    "prefix — the emitted stream stays plain greedy decode's, "
    "so k only trades dispatch count against wasted draft work.")
register_option(
    "slo", "off", choices=("off", "on"),
    doc="mx.slo per-request serving observability. 'off' (default) is "
        "the zero-overhead fast path: every serve.py hook site "
        "(submit, admit, dispatch, per-token emit, stream delivery, "
        "degradation, terminal verdict) reduces to one module-bool "
        "check — no journal object, no classification, zero "
        "allocations (asserted by ci/run.sh sanity). 'on' journals "
        "every request's event timeline, classifies each terminated "
        "request against the slo_* objectives, feeds the multi-window "
        "error-budget burn-rate gauges, and tail-samples full journals "
        "into slo_dir/<rank>/access.jsonl (render them with "
        "tools/slo_report.py). mx.slo.enable() arms at runtime.")
register_option(
    "slo_dir", "",
    "Base directory for mx.slo exemplar journals: each rank appends "
    "tail-sampled request journals, burn-rate alert records and a "
    "summary line to <dir>/<rank>/access.jsonl (meta line first). "
    "Empty (default) classifies and serves live stats only — nothing "
    "is persisted.")
register_option(
    "slo_ttft_ms", 0.0,
    "SLO objective: client-visible time-to-first-token budget per "
    "request, in milliseconds (submit to first DELIVERED token when a "
    "consumer streams, first generated token otherwise). A completed "
    "request above the budget is classified bad and burns error "
    "budget. 0 (default) disables the objective.")
register_option(
    "slo_tbt_ms", 0.0,
    "SLO objective: worst time-between-tokens budget per request, in "
    "milliseconds — the largest gap between consecutive generated "
    "tokens (a requeue's replay pause counts: the client really "
    "waited). 0 (default) disables the objective.")
register_option(
    "slo_availability", 0.999,
    "SLO objective: target fraction of non-cancelled requests that "
    "must terminate 'completed'. Rejected/shed/expired/failed "
    "requests violate it; the error budget is 1 - target, and the "
    "slo_burn_rate{window=} gauges report how fast classifications "
    "are consuming it (1.0 = exactly sustainable).")
register_option(
    "slo_burn_alert", 2.0,
    "Burn-rate alert threshold for mx.slo: when any window's error-"
    "budget burn rate reaches this multiple of the sustainable rate, "
    "an slo_alert telemetry event, a diagnostics flight-ring entry "
    "and an access-log alert record fire (once per excursion, re-"
    "armed when the window cools). The fast window reacts to a fresh "
    "overload first; the slow window confirms it is sustained.")
register_option(
    "slo_window_fast_s", 300.0,
    "Fast burn-rate window for mx.slo, in seconds (default 5 min): "
    "spikes quickly on a fresh overload, forgets quickly once the "
    "burst passes — the paging signal.")
register_option(
    "slo_window_slow_s", 3600.0,
    "Slow burn-rate window for mx.slo, in seconds (default 1 h): "
    "diluted by history, it confirms a burn is sustained rather than "
    "a blip — the ticket signal.")
register_option(
    "slo_sample_every", 10,
    "mx.slo healthy-exemplar sampling: persist every N-th classified "
    "request's full journal to access.jsonl even when it met every "
    "objective (bad, degraded and slower-than-running-p99 requests "
    "always persist). 0 persists only the tail, no healthy baseline.")
register_option(
    "scope", "off", choices=("off", "on"),
    doc="mx.scope live introspection. 'off' (default) is the "
        "zero-overhead fast path: the trainer step hook reduces to one "
        "module-bool check — no HTTP thread, no listening socket, no "
        "allocations (asserted by ci/run.sh sanity). 'on' serves the "
        "per-rank introspection endpoints on scope_port: /healthz "
        "(liveness + heartbeat age), /metrics (Prometheus text from the "
        "mx.telemetry registry, torn-read-free), /statusz (step + rate, "
        "flight-ring tail, memsafe headroom, active remat/zero/grad-"
        "accum rungs, serve stats, trace skew verdict, restart "
        "generation), /tracez (recent mx.trace spans), and "
        "/profilez?steps=N (on-demand XLA device capture around the "
        "next N trainer steps; concurrent requests get 409). "
        "tools/launch.py --scope-port arms every rank and serves the "
        "gang aggregator (tools/scope_top.py renders it live).")
register_option(
    "scope_port", 8917,
    "TCP port the mx.scope per-rank introspection server binds "
    "(127.0.0.1). 0 picks an ephemeral port (tests read it back via "
    "mx.scope.port()). Under tools/launch.py --scope-port P, rank R "
    "serves on P+1+R and the launcher's gang aggregator on P itself.")
register_option(
    "nan_sentinel", False,
    "Opt-in NaN/Inf sentinel: trainers host-fetch and finiteness-check "
    "the loss (ShardedTrainer/estimator DiagnosticsHandler) or global "
    "grad-norm (gluon Trainer) each step; a non-finite value writes a "
    "post-mortem and raises mx.diagnostics.NonFiniteError instead of "
    "silently corrupting the run. Works with diagnostics off (the dump "
    "then has an empty ring); stands down in the gluon Trainer while a "
    "scaling AMP loss scaler is attached, whose overflow-skip handles "
    "Inf grads as routine. Costs one device sync per step.")
register_option(
    "goodput", "off", choices=("off", "on"),
    doc="mx.goodput gang-level wall-clock accounting. 'off' (default) "
        "is the zero-overhead fast path: every hook site (trainer "
        "step/compile, dataflow batch-wait, checkpoint save/restore, "
        "reshard/resize, OOM-ladder recovery, serve scheduler loop) "
        "reduces to one module-bool check — no accountant state, zero "
        "allocations (asserted by ci/run.sh goodput). 'on' classifies "
        "every second of run wall-clock into exhaustive non-overlapping "
        "categories (goodput: productive step / serve decode; badput: "
        "compile, input stall, checkpoint, reshard, OOM recovery, "
        "rollback replay, serve idle/degraded) with a step-id "
        "high-water mark so re-trained steps after a rollback or "
        "restart count as badput:replay, never goodput. Merge rank "
        "files + restarts.jsonl with tools/goodput_report.py; "
        "tools/launch.py --goodput-dir arms the whole gang.")
register_option(
    "goodput_dir", "",
    "Base directory for mx.goodput interval files: each rank appends "
    "its classified wall-clock intervals to <dir>/<rank>/goodput.jsonl "
    "(meta line first, torn-line tolerant). A relaunched rank recovers "
    "its step-id high-water mark from the existing file, so replayed "
    "steps after a restart are attributed badput:replay. Empty "
    "(default) accounts in memory only — live surfaces (statusz, "
    "telemetry, post-mortem) still work; nothing is persisted.")
register_option(
    "ledger_dir", "",
    "Base directory for the mx.ledger cross-run performance ledger: "
    "every bench entrypoint and the ci tier-1 sweep append one "
    "provenance-keyed record per run to <dir>/ledger.jsonl (append-"
    "only, torn-line tolerant). Empty (default) is the zero-overhead "
    "fast path — every hook site reduces to one module-bool check and "
    "makes zero record calls (asserted by ci/run.sh). Render, "
    "backfill and gate the history with tools/ledger_report.py.")
register_option(
    "ledger_gate", "error", choices=("warn", "error"),
    doc="mx.ledger trend-gate severity for ci/run.sh's ledger stage: "
        "'error' (default) exits nonzero when the drift detector "
        "CONFIRMS a regression in a like-provenance metric series "
        "(same platform, device count, smoke flag and config "
        "fingerprint — CPU-smoke history never gates a TPU number); "
        "'warn' reports the same verdicts but always exits zero. "
        "Smoke-mode series and unconfirmed 'suspect' drifts only ever "
        "warn, whatever this knob says.")
