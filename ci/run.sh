#!/bin/sh
# CI harness (reference: the upstream ci/ Jenkins matrix — build_windows /
# sanity / unittest / nightly stages). Stages here map to what this
# framework actually has; each is independently invokable:
#
#   ci/run.sh sanity      — import + compile-surface checks, fast
#   ci/run.sh static      — mx.check static analysis: AST rules, graph
#                           lint over the model zoo, tsan-lite lock sweep
#   ci/run.sh unittest    — tests/unittest on the 8-device virtual CPU mesh
#   ci/run.sh dist        — tests/dist (sharding/collectives/pipeline/mp)
#   ci/run.sh train       — tests/train (convergence-tier, slower)
#   ci/run.sh native      — build + test the C++ data pipeline
#   ci/run.sh pages       — mx.pages paged serving: off-path
#                           zero-overhead, shared-prefix bit-identity,
#                           interpret-mode kernel parity
#   ci/run.sh goodput     — mx.goodput wall-clock accounting: off-path
#                           zero-overhead, seeded kill@step fault run
#                           whose report must attribute restart downtime
#                           and replayed steps correctly
#   ci/run.sh fleet       — mx.fleet replicated serving: off-path
#                           zero-overhead, kill-a-replica-mid-load smoke
#                           (zero accepted requests lost, restarts.jsonl
#                           records the relaunch)
#   ci/run.sh all         — everything + the driver-contract gate
set -e
cd "$(dirname "$0")/.."

stage="${1:-all}"

sanity() {
    echo "== sanity =="
    JAX_PLATFORMS=cpu python -c "
import jax; jax.config.update('jax_platforms', 'cpu')
import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, symbol, parallel, models, contrib
from mxnet_tpu.contrib import onnx
from mxnet_tpu.ops import OPS
assert len(OPS) > 200, len(OPS)
print('import surface OK:', len(OPS), 'ops')
"
    # telemetry must be disabled by default and its disabled fast path must
    # not count, allocate events, or touch the registry lock per increment
    JAX_PLATFORMS=cpu python -c "
from mxnet_tpu import telemetry
assert not telemetry.enabled(), 'telemetry must default to off'
c = telemetry.counter('ci_sanity_probe_total')
h = telemetry.histogram('ci_sanity_probe_seconds')
c.inc(); h.observe(1.0); telemetry.event('step', dur_s=1.0)
assert c.value == 0 and h.count == 0, 'disabled metric still counted'
assert telemetry.events() == [], 'disabled fast path allocated events'
print('telemetry disabled fast path OK')
"
    # the async sharded-step hot path with telemetry+diagnostics disabled
    # must be fence-free and transfer-free: zero block_until_ready, zero
    # device_put (batches pre-staged by prefetch_to_mesh are reused as-is),
    # zero host->device scalar conversions (t/lr live on device / in-jit)
    JAX_PLATFORMS=cpu python -c "
import numpy as np, jax, jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, dataflow, telemetry, diagnostics
from mxnet_tpu.gluon import nn, loss as gloss
assert not telemetry.enabled() and not diagnostics.enabled()
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
batches = list(dataflow.prefetch_to_mesh(iter([([x], [y])] * 6), tr, depth=2))
tr.step_async(*batches[0])   # compile outside the counted window
counts = {'fence': 0, 'device_put': 0, 'asarray': 0}
real = (jax.block_until_ready, jax.device_put, jnp.asarray)
jax.block_until_ready = lambda v: (counts.__setitem__('fence', counts['fence'] + 1), real[0](v))[1]
jax.device_put = lambda *a, **k: (counts.__setitem__('device_put', counts['device_put'] + 1), real[1](*a, **k))[1]
jnp.asarray = lambda *a, **k: (counts.__setitem__('asarray', counts['asarray'] + 1), real[2](*a, **k))[1]
try:
    for d, l in batches[1:]:
        tr.step_async(d, l)
finally:
    jax.block_until_ready, jax.device_put, jnp.asarray = real
assert counts == {'fence': 0, 'device_put': 0, 'asarray': 0}, counts
print('async step disabled fast path OK (no fence, no transfers)')
"
    # inspect must be disabled by default: the step path makes zero
    # cost_analysis/memory_analysis calls (no analysis lower+compile) and
    # allocates no CostRecords — the hook sites reduce to one bool check
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, telemetry, diagnostics
from mxnet_tpu import inspect as mxi
from mxnet_tpu.gluon import nn, loss as gloss
assert not mxi.enabled(), 'inspect must default to off'
calls = {'analyze': 0, 'record': 0, 'note': 0}
real = (mxi.analyze_jit, mxi.record_compiled, mxi.note_step)
mxi.analyze_jit = lambda *a, **k: (calls.__setitem__('analyze', calls['analyze'] + 1), real[0](*a, **k))[1]
mxi.record_compiled = lambda *a, **k: (calls.__setitem__('record', calls['record'] + 1), real[1](*a, **k))[1]
mxi.note_step = lambda *a, **k: (calls.__setitem__('note', calls['note'] + 1), real[2](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
net2 = nn.Dense(4, in_units=8); net2.initialize(); net2.hybridize()
net2(x)
mxi.analyze_jit, mxi.record_compiled, mxi.note_step = real
assert calls == {'analyze': 0, 'record': 0, 'note': 0}, calls
assert mxi.records() == [], 'disabled fast path allocated CostRecords'
print('inspect disabled fast path OK (no analysis calls, no records)')
"
    # the measuring scripts have no CPU fallback: on a machine without
    # a TPU each must exit non-zero and print no row (a number they print
    # goes under a device metric's name). bench_dataloader is the host
    # benchmark and is exempt.
    for script in chip_smoke.py bench.py \
            benchmarks/bench_generate.py benchmarks/bench_kernels.py \
            benchmarks/bench_attention.py benchmarks/bench_step_profile.py \
            benchmarks/bench_resnet.py; do
        if JAX_PLATFORMS=cpu python "$script" \
                > /tmp/_nochip.out 2>/tmp/_nochip.err; then
            echo "$script exited 0 without a TPU" >&2
            exit 1
        fi
        if grep -q '^{' /tmp/_nochip.out; then
            echo "$script printed a result row without a TPU" >&2
            exit 1
        fi
        grep -q "cpu" /tmp/_nochip.err /tmp/_nochip.out
    done
    echo "no-chip refusal OK (chip_smoke, bench.py, benchmarks/*)"
    # the smoke's CPU rehearsal (slow-marked out of the tier-1 sweep):
    # every phase end to end at tiny sizes through the interpreter
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        tests/unittest/test_chip_smoke.py::test_rehearsal_runs_every_phase_and_claims_nothing
    # mx.check must be disabled by default: the trainer and block hot
    # paths make zero analyzer calls (one module-bool check each), no
    # jaxpr is traced, and no findings registry accumulates
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, check
from mxnet_tpu.gluon import nn, loss as gloss
assert not check.enabled(), 'check must default to off'
calls = {'jit': 0, 'step': 0, 'lint': 0}
real = (check.check_jit, check.check_step, check.lint_jaxpr)
check.check_jit = lambda *a, **k: (calls.__setitem__('jit', calls['jit'] + 1), real[0](*a, **k))[1]
check.check_step = lambda *a, **k: (calls.__setitem__('step', calls['step'] + 1), real[1](*a, **k))[1]
check.lint_jaxpr = lambda *a, **k: (calls.__setitem__('lint', calls['lint'] + 1), real[2](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
net2 = nn.Dense(4, in_units=8); net2.initialize(); net2.hybridize()
net2(x)
check.check_jit, check.check_step, check.lint_jaxpr = real
assert calls == {'jit': 0, 'step': 0, 'lint': 0}, calls
assert check.findings() == [], 'disabled fast path recorded findings'
print('check disabled fast path OK (no lint calls, no findings)')
"
    # memsafe must be disabled by default (oom_recover=off): the trainer
    # and block hot paths make zero preflight/capacity/recovery calls (one
    # module-bool check each), no budget state accumulates, and no
    # degradation handler runs — the zero-overhead fast path
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, memsafe
from mxnet_tpu.gluon import nn, loss as gloss
assert not memsafe.enabled(), 'memsafe must default to off'
calls = {'pre_step': 0, 'pre_jit': 0, 'cap': 0, 'recover': 0}
real = (memsafe.preflight_step, memsafe.preflight_jit,
        memsafe.capacity_bytes, memsafe.recover_trainer)
memsafe.preflight_step = lambda *a, **k: (calls.__setitem__('pre_step', calls['pre_step'] + 1), real[0](*a, **k))[1]
memsafe.preflight_jit = lambda *a, **k: (calls.__setitem__('pre_jit', calls['pre_jit'] + 1), real[1](*a, **k))[1]
memsafe.capacity_bytes = lambda *a, **k: (calls.__setitem__('cap', calls['cap'] + 1), real[2](*a, **k))[1]
memsafe.recover_trainer = lambda *a, **k: (calls.__setitem__('recover', calls['recover'] + 1), real[3](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
net2 = nn.Dense(4, in_units=8); net2.initialize(); net2.hybridize()
net2(x)
memsafe.preflight_step, memsafe.preflight_jit, memsafe.capacity_bytes, \\
    memsafe.recover_trainer = real
assert calls == {'pre_step': 0, 'pre_jit': 0, 'cap': 0, 'recover': 0}, calls
assert memsafe.transitions() == [], 'disabled fast path recorded transitions'
assert memsafe.last_check() is None, 'disabled fast path ran a budget check'
print('memsafe disabled fast path OK (no preflight, no capacity probes)')
"
    # memsafe acceptance (slow-marked out of the tier-1 sweep): a config
    # exceeding a simulated device_bytes_limit is rejected pre-dispatch
    # and — under oom_recover=auto — degrades and trains to completion
    # with loss parity; remat policies are loss-bit-exact; autofit bucket
    # boundaries feed BucketPad
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_memsafe.py::test_budget_driven_recovery_trains_to_completion \
        tests/unittest/test_memsafe.py::test_remat_policy_equivalence_bit_exact \
        tests/unittest/test_memsafe.py::test_autofit_bucket_boundaries_feed_bucket_pad \
        -q -p no:cacheprovider
    # autofit smoke under a simulated capacity: the chosen batch's
    # predicted peak fits, the next-larger candidate's does not, and no
    # device step executed (pure AOT analysis)
    JAX_PLATFORMS=cpu python -c "
import json, subprocess, sys
r = subprocess.run(
    [sys.executable, 'tools/autofit.py', '--model', 'dense',
     '--max-batch', '1024', '--device-bytes-limit', '700000'],
    capture_output=True, text=True, timeout=240)
assert r.returncode == 0, r.stderr[-2000:]
d = json.loads([l for l in r.stdout.splitlines() if l.startswith('{')][0])
assert d['predicted_bytes'] <= d['capacity_bytes'], d
assert d['next_larger'] and \\
    d['next_larger']['predicted_bytes'] > d['capacity_bytes'], d
print('autofit smoke OK: batch', d['batch_size'], 'predicted',
      d['predicted_bytes'], 'of', d['capacity_bytes'])
"
    # zero must be disabled by default (zero=off): trainer construction
    # and the step make ZERO calls into the mx.zero module — no state
    # planning, no flat-spec probe, no in-step sharding constraint — and
    # the optimizer state stays in its parameter's sharding
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel
from mxnet_tpu.parallel import zero
from mxnet_tpu.gluon import nn, loss as gloss
assert not zero.enabled(), 'zero must default to off'
calls = {'plan': 0, 'flat': 0, 'spec': 0, 'constrain': 0}
real = (zero.plan_state, zero.flat_spec, zero.zero_spec, zero.constrain)
zero.plan_state = lambda *a, **k: (calls.__setitem__('plan', calls['plan'] + 1), real[0](*a, **k))[1]
zero.flat_spec = lambda *a, **k: (calls.__setitem__('flat', calls['flat'] + 1), real[1](*a, **k))[1]
zero.zero_spec = lambda *a, **k: (calls.__setitem__('spec', calls['spec'] + 1), real[2](*a, **k))[1]
zero.constrain = lambda *a, **k: (calls.__setitem__('constrain', calls['constrain'] + 1), real[3](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'adam',
                             {'learning_rate': 0.01})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
zero.plan_state, zero.flat_spec, zero.zero_spec, zero.constrain = real
assert calls == {'plan': 0, 'flat': 0, 'spec': 0, 'constrain': 0}, calls
assert tr._zero is False and tr._zero_specs is None \
    and tr._zero_flat is None, 'zero state armed while disabled'
print('zero disabled fast path OK (no planning, no constraints)')
"
    # mx.kernels fast path: a kernels=off run must keep the trainer hot
    # loop entirely pallas-free — no jax.experimental.pallas import (the
    # adam step and the QuantizedDense int8 forward route through their
    # XLA-native fallbacks), and the kernels=auto default on a CPU
    # backend behaves identically (backend probe first, no import)
    JAX_PLATFORMS=cpu python -c "
import sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, config
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.contrib import quantization as Q
config.set('kernels', 'off')
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'adam',
                             {'learning_rate': 0.01})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
d = nn.Dense(4, in_units=8); d.initialize()
Q.QuantizedDense(d)(nd.array(np.ones((2, 8), np.float32)))
assert 'jax.experimental.pallas' not in sys.modules, \
    'kernels=off hot loop imported pallas'
# CPU backend under kernels=auto must behave identically — and the
# assert must see a FRESH trace (a cached executable would never
# re-consult the knob): new net+trainer and a new quantized forward
config.set('kernels', 'auto')
net2 = nn.Dense(4, in_units=8); net2.initialize()
tr2 = parallel.ShardedTrainer(net2, lambda o, l: lfn(o, l), 'adam',
                              {'learning_rate': 0.01})
for _ in range(2):
    tr2.step(x, y)
d2 = nn.Dense(4, in_units=8); d2.initialize()
Q.QuantizedDense(d2)(nd.array(np.ones((2, 8), np.float32)))
assert 'jax.experimental.pallas' not in sys.modules, \
    'kernels=auto on CPU imported pallas'
print('kernels=off fast path OK (no pallas import on the hot loop)')
"
    # interpret-mode kernel suite: the kernel CODE (not the jnp
    # fallback) for all three new kernels — int8 matmul, fused update,
    # MoE dispatch/combine — parity-tested through the Pallas
    # interpreter on CPU (the same pattern as test_flash_interpret)
    MXNET_TPU_PALLAS_INTERPRET=1 JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_kernels.py -q \
        -p no:cacheprovider
    # resilience must be disabled by default: no signal handlers installed,
    # the trainer step hook reduces to one module-bool check (zero on_step
    # calls), and save/restore do no manifest hashing (zero _file_crc
    # calls, no manifest.json on disk)
    JAX_PLATFORMS=cpu python -c "
import os, signal, tempfile
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, resilience
from mxnet_tpu.gluon import nn, loss as gloss
assert not resilience.enabled(), 'resilience must default to off'
assert signal.getsignal(signal.SIGTERM) is not resilience._on_signal, \
    'SIGTERM handler installed while disabled'
assert signal.getsignal(signal.SIGINT) is not resilience._on_signal, \
    'SIGINT handler installed while disabled'
calls = {'on_step': 0, 'crc': 0, 'fault': 0}
real = (resilience.on_step, resilience._file_crc, resilience.fault_point)
resilience.on_step = lambda *a, **k: (calls.__setitem__('on_step', calls['on_step'] + 1), real[0](*a, **k))[1]
resilience._file_crc = lambda *a, **k: (calls.__setitem__('crc', calls['crc'] + 1), real[1](*a, **k))[1]
resilience.fault_point = lambda *a, **k: (calls.__setitem__('fault', calls['fault'] + 1), real[2](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
d = tempfile.mkdtemp()
tr.save_states(os.path.join(d, 'ck'))
tr.load_states(os.path.join(d, 'ck'))
resilience.on_step, resilience._file_crc, resilience.fault_point = real
assert calls == {'on_step': 0, 'crc': 0, 'fault': 0}, calls
assert not os.path.exists(os.path.join(d, 'ck', 'manifest.json')), \
    'manifest written while resilience disabled'
print('resilience disabled fast path OK (no handlers, no hashing)')
"
    # fault-injection smoke: 2-rank launch, rank 1 SIGKILLed at step 3,
    # supervised relaunch auto-resumes from the last good checkpoint and
    # the final loss matches an uninterrupted run bit-exactly
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_resilience.py::test_kill_and_relaunch_resumes_bit_exact \
        -q -p no:cacheprovider
    # trace must not be live by default (off, and no profiler session):
    # the trainer/dataflow/block hook sites read trace.live() and make
    # zero span, recorder, skew-probe or annotation calls, and no span
    # buffer exists — the zero-overhead fast path
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, dataflow, trace
from mxnet_tpu.gluon import nn, loss as gloss
assert not trace.enabled() and not trace.live(), 'trace must default to off'
calls = {'span': 0, 'skew': 0, 'ann': 0, 'live_span': 0}
real = (trace.record_span, trace.skew_tick, trace.annotate, trace.span)
trace.record_span = lambda *a, **k: (calls.__setitem__('span', calls['span'] + 1), real[0](*a, **k))[1]
trace.skew_tick = lambda *a, **k: (calls.__setitem__('skew', calls['skew'] + 1), real[1](*a, **k))[1]
trace.annotate = lambda *a, **k: (calls.__setitem__('ann', calls['ann'] + 1), real[2](*a, **k))[1]
trace.span = lambda *a, **k: (calls.__setitem__('live_span', calls['live_span'] + 1), real[3](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for d, l in dataflow.prefetch_to_mesh(iter([([x], [y])] * 3), tr, depth=2):
    tr.step(d, l)
net2 = nn.Dense(4, in_units=8); net2.initialize(); net2.hybridize()
net2(x)
trace.record_span, trace.skew_tick, trace.annotate, trace.span = real
assert calls == {'span': 0, 'skew': 0, 'ann': 0, 'live_span': 0}, calls
assert trace._buf is None, 'disabled fast path allocated the span buffer'
assert trace.spans() == [], 'disabled fast path recorded spans'
print('trace disabled fast path OK (no recorder calls, no buffer)')
"
    # trace acceptance: 2-rank launch with an injected input stall on
    # rank 1 -> per-rank span files merge into one clock-aligned Perfetto
    # trace and the gang verdict names rank 1 as the input-bound straggler
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_trace.py::test_two_rank_straggler_report_names_rank1 \
        -q -p no:cacheprovider
    # guard must be disabled by default: the trainer/dataflow hook sites
    # make zero guard calls (one module-bool check each), no heartbeat
    # record or file exists, and no collective-deadline thread runs —
    # the zero-overhead fast path
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, dataflow, guard
from mxnet_tpu.gluon import nn, loss as gloss
assert not guard.enabled(), 'guard must default to off'
calls = {'beat': 0, 'begin': 0, 'step': 0, 'sdc': 0}
real = (guard.heartbeat, guard.step_begin, guard.on_step, guard.sdc_check)
guard.heartbeat = lambda *a, **k: (calls.__setitem__('beat', calls['beat'] + 1), real[0](*a, **k))[1]
guard.step_begin = lambda *a, **k: (calls.__setitem__('begin', calls['begin'] + 1), real[1](*a, **k))[1]
guard.on_step = lambda *a, **k: (calls.__setitem__('step', calls['step'] + 1), real[2](*a, **k))[1]
guard.sdc_check = lambda *a, **k: (calls.__setitem__('sdc', calls['sdc'] + 1), real[3](*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for d, l in dataflow.prefetch_to_mesh(iter([([x], [y])] * 3), tr, depth=2):
    tr.step(d, l)
guard.heartbeat, guard.step_begin, guard.on_step, guard.sdc_check = real
assert calls == {'beat': 0, 'begin': 0, 'step': 0, 'sdc': 0}, calls
assert guard._beat is None, 'disabled fast path recorded a heartbeat'
assert guard._deadline is None, 'deadline armed while disabled'
print('guard disabled fast path OK (no beats, no deadline, no digests)')
"
    # guard acceptance smokes: (a) an injected hang on rank 1 goes
    # heartbeat-stale, the supervisor kills the stuck-but-alive rank
    # within --heartbeat-timeout, and the --elastic relaunch completes
    # the run (restarts.jsonl records the slot loss); (b) an injected
    # gradient bit-flip on rank 0 is caught by the SDC digest vote,
    # attributed to rank 0 by majority, and rolled back to the last
    # verified checkpoint with a bit-exact final loss on both ranks
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_guard.py::test_hang_detected_killed_and_relaunched \
        tests/unittest/test_guard.py::test_corrupt_grad_vote_restores_bit_exact \
        -q -p no:cacheprovider
    # serve must be disabled by default: the shared decode dispatch site
    # (jit_flat_step) makes zero note_dispatch calls while no Server
    # exists and the knob is off — the zero-overhead fast path; a
    # constructed Server arms it
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import parallel, serve
from mxnet_tpu.models import gpt as gpt_mod
assert not serve.enabled(), 'serve must default to off'
calls = {'dispatch': 0}
real = serve.note_dispatch
serve.note_dispatch = lambda *a, **k: (calls.__setitem__('dispatch', calls['dispatch'] + 1), real(*a, **k))[1]
parallel.make_mesh(dp=-1)
model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
mx.random.seed(0); model.initialize()
model.generate(np.arange(4, dtype=np.int32)[None], max_new_tokens=4,
               on_device=False)
serve.note_dispatch = real
assert calls == {'dispatch': 0}, calls
assert serve.dispatches() == 0, 'disabled fast path counted dispatches'
print('serve disabled fast path OK (no decode-hook calls)')
"
    # slo must be disabled by default: a full request lifecycle through
    # a real Server makes ZERO mx.slo hook calls and allocates no
    # journal (the hook sites reduce to one module-bool check) — then
    # the armed path's access.jsonl must honor the schema contract
    # (meta line first, schema-versioned access records with the
    # per-phase attribution, summary last)
    JAX_PLATFORMS=cpu python -c "
import json, os, shutil
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import parallel, serve, slo
from mxnet_tpu.models import gpt as gpt_mod
assert not slo.enabled(), 'slo must default to off'
hooks = ('note_submit', 'note_admit', 'note_first_dispatch',
         'note_token', 'note_event', 'note_stream_start',
         'note_delivered', 'note_stream_end', 'note_finish')
calls = {h: 0 for h in hooks}
real = {h: getattr(slo, h) for h in hooks}
for h in hooks:
    setattr(slo, h, lambda *a, _h=h, **k: calls.__setitem__(_h, calls[_h] + 1))
parallel.make_mesh(dp=-1)
model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
mx.random.seed(0); model.initialize()
srv = serve.Server(model, slots=2)
r = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=4)
srv.drain()
assert r.state == serve.DONE
assert calls == {h: 0 for h in hooks}, calls
assert r._slo_j is None, 'disabled fast path allocated a journal'
for h in hooks:
    setattr(slo, h, real[h])
shutil.rmtree('/tmp/_ci_slo', ignore_errors=True)
slo.enable(slo_dir='/tmp/_ci_slo', rank=0, sample_every=1)
r2 = srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=4)
srv.drain()
assert r2.state == serve.DONE
slo.disable()
recs = [json.loads(l) for l in open('/tmp/_ci_slo/0/access.jsonl')]
kinds = [rec['kind'] for rec in recs]
assert kinds[0] == 'meta' and 'access' in kinds and kinds[-1] == 'summary', kinds
meta = recs[0]
assert meta['schema'] == 1 and 'objectives' in meta and 'rank' in meta, meta
acc = next(rec for rec in recs if rec['kind'] == 'access')
for k in ('schema', 'rank', 'req', 'outcome', 'verdict', 'good',
          'violations', 'why', 'prompt_len', 'requested_new',
          'new_tokens', 'delivered', 'requeues', 'degraded', 'retries',
          'queue_ms', 'prefill_ms', 'decode_ms', 'stream_ms', 'ttft_ms',
          'tbt_max_ms', 'tbt_p99_ms', 'submit_us', 'timeline'):
    assert k in acc, f'access record missing {k}: {sorted(acc)}'
evs = [e['event'] for e in acc['timeline']]
assert evs[0] == 'submit' and 'first_token' in evs and 'finish' in evs, evs
ts = [e['t_ms'] for e in acc['timeline']]
assert ts == sorted(ts), 'timeline must be monotone'
summ = recs[-1]
assert 'burn_rate' in summ and 'counts' in summ, sorted(summ)
print('slo disabled fast path OK (zero hook calls) + access.jsonl schema OK')
"
    # serving acceptance smoke (slow-marked out of the tier-1 sweep):
    # queue full + slow client + mid-generation cancel + deadline expiry
    # + forced memory rejection at admission — the scheduler never
    # raises, never dispatches a predicted-overrun batch, evicts expired
    # slots between decode steps, and every completed request's tokens
    # are bit-identical to its unloaded single-request generation; plus
    # the mx.slo 2-rank overload acceptance: merged access logs must
    # blame the QUEUE for the p99 TTFT and alert on the fast window
    # first
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_serve.py::test_overload_acceptance_smoke \
        tests/unittest/test_slo.py::test_two_rank_overload_smoke \
        -q -p no:cacheprovider
    # scope must be disabled by default: the trainer hook site makes zero
    # on_step calls (one module-bool check), no introspection state or
    # HTTP thread is allocated, and nothing listens on scope_port — the
    # zero-thread/zero-allocation fast path
    JAX_PLATFORMS=cpu python -c "
import socket, threading
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, scope, config
from mxnet_tpu.gluon import nn, loss as gloss
assert not scope.enabled(), 'scope must default to off'
# probe against a port WE pick (free a moment ago): asserting on the
# global default 8917 would fail spuriously whenever an unrelated
# process on the host holds it
probe = socket.socket(); probe.bind(('127.0.0.1', 0))
free_port = probe.getsockname()[1]; probe.close()
config.set('scope_port', free_port)
calls = {'on_step': 0}
real = scope.on_step
scope.on_step = lambda *a, **k: (calls.__setitem__('on_step', calls['on_step'] + 1), real(*a, **k))[1]
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for _ in range(3):
    tr.step(x, y)
scope.on_step = real
assert calls == {'on_step': 0}, calls
assert scope._state is None and scope._server is None, \
    'scope state allocated while disabled'
assert not any(t.name == 'mx-scope-server'
               for t in threading.enumerate()), 'scope thread exists'
s = socket.socket()
try:
    rc = s.connect_ex(('127.0.0.1', free_port))
finally:
    s.close()
assert rc != 0, 'something listens on scope_port while scope is off'
print('scope disabled fast path OK (no hook calls, no thread, no socket)')
"
    # scope acceptance smokes: (a) a 2-rank --scope-port gang serves
    # /healthz + /metrics on BOTH rank ports while training, the
    # aggregator /statusz names both ranks at (nearly) the same step,
    # and ONE aggregator /profilez?steps=2 captures a non-empty device
    # trace dir on every rank; (b) under an injected hang@step on
    # rank 1, the healthy rank's /statusz and the aggregator still
    # answer within their timeouts and the gang view names rank 1 as
    # stale — a wedged peer never blocks the introspection plane
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_scope.py::test_two_rank_scope_smoke \
        tests/unittest/test_scope.py::test_hang_statusz_stays_live_names_stale_rank \
        -q -p no:cacheprovider
    # diagnostics must be disabled by default: no ring-buffer allocation,
    # no recorded entries, and no watchdog thread on the disabled fast path
    JAX_PLATFORMS=cpu python -c "
import threading
from mxnet_tpu import diagnostics
assert not diagnostics.enabled(), 'diagnostics must default to off'
diagnostics.record_step(1, loss=0.5, lr=1e-3)
diagnostics.record_event('compile', block='X')
assert diagnostics._ring is None, 'disabled fast path allocated the ring'
assert diagnostics.records() == [], 'disabled fast path recorded entries'
assert diagnostics._watchdog is None, 'watchdog armed while disabled'
assert not any(t.name == 'mx-diagnostics-watchdog'
               for t in threading.enumerate()), 'watchdog thread exists'
print('diagnostics disabled fast path OK')
"
}

static_stage() {
    echo "== static =="
    # AST rules over the whole tree: shard-map-import (bit PR 5 and 6),
    # signal-handler-blocking (PR 5's launch.py deadlock), raw-lock,
    # wallclock-in-jit. Exits nonzero on any unsuppressed finding.
    python tools/lint_rules.py
    # graph lint over the standard model zoo: the repo's own models must
    # compile with ZERO findings (large constants, donation misses,
    # dtype promotions, degenerate sharding, retrace hazards)
    JAX_PLATFORMS=cpu python tools/check_graph.py \
        --model dense --model bert_tiny --model gpt_tiny --steps 2
    # tsan-lite sweep: re-run the threaded unit tests with the
    # instrumented-lock layer armed — any lock-order cycle or unguarded
    # shared-structure mutation raises LockOrderError and fails the test
    # that exposed it
    MXNET_TPU_CHECK_THREADS=1 JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_telemetry.py tests/unittest/test_check.py \
        tests/unittest/test_dataflow.py tests/unittest/test_inspect.py \
        tests/unittest/test_trace.py tests/unittest/test_guard.py \
        tests/unittest/test_serve.py tests/unittest/test_scope.py \
        tests/unittest/test_fleet.py \
        -q -m 'not slow' -p no:cacheprovider
    # the heavier scope acceptance tests ride here instead of the tier-1
    # sweep (the PR 5 slow-marking pattern): the bit-identical-loss gate
    # for /profilez on a live trainer, the blocking-wait capture, the
    # black-hole fan-out bound, and the scope_top CLI round trips
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_scope.py::test_scope_on_loss_trajectory_bit_identical \
        tests/unittest/test_scope.py::test_profilez_blocking_wait_returns_200 \
        tests/unittest/test_scope.py::test_aggregator_not_wedged_by_silent_rank \
        tests/unittest/test_scope.py::test_scope_top_renders_once \
        tests/unittest/test_scope.py::test_scope_top_unreachable_aggregator_exits_nonzero \
        tests/unittest/test_scope.py::test_profilez_capture_and_409_on_concurrent \
        -q -p no:cacheprovider
}

unittest_stage() {
    echo "== unittest =="
    # covers tests/unittest/test_telemetry.py (registry semantics,
    # recompile-cause events, exporters) along with everything else.
    # -m 'not slow': the heavy end-to-end tests (e.g. the resilience
    # kill-and-relaunch smoke, already run by the sanity stage) live
    # behind the slow marker
    t0=$(date +%s)
    rc=0
    python -m pytest tests/unittest -q -m 'not slow' --durations=10 \
        > /tmp/_tier1_sweep.log 2>&1 || rc=$?
    cat /tmp/_tier1_sweep.log
    wall=$(( $(date +%s) - t0 ))
    # the unittest tests slow-marked out of the tier-1 filter for the
    # time budget (unlike tests/train, nothing else reruns tests/unittest
    # unfiltered) — run them explicitly so they stay covered every pass
    python -m pytest \
        tests/unittest/test_contrib.py::test_quantize_resnet18_end_to_end \
        tests/unittest/test_models.py::test_resnet18_trains \
        tests/unittest/test_models.py::test_resnet50_shapes_and_grad \
        tests/unittest/test_bert_finetune.py::test_qa_finetune_overfits_tiny \
        tests/unittest/test_flash_interpret.py::test_interpret_ring_pallas_inner \
        "tests/unittest/test_model_zoo.py::test_zoo_forward_shapes[densenet121-64]" \
        "tests/unittest/test_model_zoo.py::test_zoo_forward_shapes[inceptionv3-96]" \
        "tests/unittest/test_model_zoo.py::test_zoo_forward_shapes[mobilenetv2_0.5-224]" \
        -q -p no:cacheprovider || rc=$?
    if [ -n "${MXNET_TPU_LEDGER_DIR:-}" ]; then
        # tier-1 time-budget tracking: sweep wall time, pass/fail
        # counts and the top-10 slowest tests become a ledger record
        # (ledger_report prints the budget burn, warning above 85% of
        # the 870 s timeout); best-effort — never fails the sweep
        python tools/ledger_report.py --record-tier1 \
            /tmp/_tier1_sweep.log --wall "$wall" || true
    fi
    return $rc
}

dist_stage() {
    echo "== dist =="
    python -m pytest tests/dist -q
    # elastic acceptance: train 4-way, SIGKILL the gang at step 3, the
    # --elastic supervisor relaunches at the surviving world size, the
    # resumed worker reshards the 4-way checkpoint onto a 2-way mesh, and
    # the loss trajectory matches the uninterrupted run
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_reshard.py::test_elastic_kill_shrink_resume_matches_reference \
        -q -p no:cacheprovider
    # mx.zero acceptance: 4-way zero'd training matches the unsharded
    # reference loss trajectory step for step, then a kill-shrink
    # elastic relaunch restores the sharded state bit-exactly onto the
    # 2-way mesh and finishes (reporting the measured per-device
    # opt-state byte drop along the way)
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_zero.py::test_zero_elastic_kill_shrink_acceptance \
        -q -p no:cacheprovider
}

train_stage() {
    echo "== train =="
    python -m pytest tests/train -q
}

native_stage() {
    echo "== native =="
    make -C native >/dev/null
    python -m pytest tests/unittest/test_native_io.py -q
}

ledger_stage() {
    echo "== ledger =="
    # the ledger must default off: a bench-side ledger_append and a
    # tier-1 record with the knob unset make ZERO record/append calls
    # (the hook sites reduce to one module-bool check) and write nothing
    JAX_PLATFORMS=cpu python -c "
import os
assert not os.environ.get('MXNET_TPU_LEDGER_DIR'), \
    'run the off-path assert with the knob unset'
from mxnet_tpu import ledger
from benchmarks import _provenance
assert not ledger.enabled(), 'ledger must default to off'
calls = {'record': 0, 'append': 0}
real = (ledger.record_run, ledger.append_record)
ledger.record_run = lambda *a, **k: (calls.__setitem__('record', calls['record'] + 1), real[0](*a, **k))[1]
ledger.append_record = lambda *a, **k: (calls.__setitem__('append', calls['append'] + 1), real[1](*a, **k))[1]
out = _provenance.ledger_append('bench.py', [{'metric': 'm', 'value': 1.0}])
t1 = ledger.record_tier1(10.0, 5, 0)
ledger.record_run, ledger.append_record = real
assert out is None and t1 is None, (out, t1)
assert calls == {'record': 0, 'append': 0}, calls
print('ledger disabled fast path OK (zero record calls, nothing written)')
"
    # seeded-regression acceptance: a synthetic 30%-degraded
    # like-provenance run must turn the gate red NAMING the metric and
    # the first bad run, while the SAME degraded row under smoke-mode
    # provenance only warns
    SEED_DIR=$(mktemp -d)
    MXNET_TPU_LEDGER_SEED_DIR="$SEED_DIR" python -c "
import importlib.util, os
spec = importlib.util.spec_from_file_location('mx_ledger',
                                              'mxnet_tpu/ledger.py')
led = importlib.util.module_from_spec(spec)
spec.loader.exec_module(led)
path = os.path.join(os.environ['MXNET_TPU_LEDGER_SEED_DIR'],
                    'ledger.jsonl')
tpu = led.build_provenance(platform='tpu', devices=4, smoke_mode=False,
                           rev='seed', fingerprint='cafef00d', knobs={})
smk = led.build_provenance(platform='cpu', devices=1, smoke_mode=True,
                           rev='seed', fingerprint='cafef00d', knobs={})
metric = 'bert_base_pretrain_tokens_per_sec_per_chip'
for i, v in enumerate([100000, 101000, 99500, 100500, 100200]):
    for prov in (tpu, smk):
        led.append_record(path, led.build_run_record(
            'bench.py', [{'metric': metric, 'value': v}],
            provenance=prov, ts=1000.0 + i, label='run%d' % i))
for prov in (tpu, smk):
    led.append_record(path, led.build_run_record(
        'bench.py', [{'metric': metric, 'value': 70000}],
        provenance=prov, ts=1010.0, label='degraded-run'))
print('seeded regression ledger at', path)
"
    seed_rc=0
    python tools/ledger_report.py "$SEED_DIR" --gate \
        > /tmp/_ledger_gate.out 2>&1 || seed_rc=$?
    cat /tmp/_ledger_gate.out
    if [ "$seed_rc" -ne 1 ]; then
        echo "seeded regression must exit 1, got $seed_rc" >&2
        exit 1
    fi
    grep -q "CONFIRMED regression: bert_base_pretrain_tokens_per_sec_per_chip" \
        /tmp/_ledger_gate.out
    grep -q "first bad run: degraded-run" /tmp/_ledger_gate.out
    grep -q "warn (smoke-mode provenance)" /tmp/_ledger_gate.out
    # the same confirmed regression under ledger_gate=warn is
    # downgraded to exit 0 (the verdicts still print)
    MXNET_TPU_LEDGER_GATE=warn python tools/ledger_report.py \
        "$SEED_DIR" --gate > /dev/null
    rm -rf "$SEED_DIR"
    echo "ledger stage OK: off-path contract, seeded-regression gate"
}

pages_stage() {
    echo "== pages =="
    # shared-prefix smoke: the server must emit BIT-IDENTICAL token
    # streams to model.generate on prompts sharing a prefix, with the
    # prefix tree actually reusing blocks (hit rate > 0) and prefill
    # running chunked (fewer dispatches than prompt tokens)
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import parallel, serve
from mxnet_tpu.models import gpt as gpt_mod
parallel.make_mesh(dp=-1)
model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
mx.random.seed(0); model.initialize()
rng = np.random.RandomState(7)
pre = rng.randint(0, 128, (16,)).astype(np.int32)
prompts = [np.concatenate([pre, rng.randint(0, 128, (n,)).astype(np.int32)])
           for n in (3, 5, 2, 6)]
srv = serve.Server(model, slots=2, page_size=8, prefill_chunk=4)
reqs = [srv.submit(p, max_new_tokens=6) for p in prompts]
srv.drain()
st = srv.stats()
srv.stop()
assert all(r.state == serve.DONE for r in reqs), [r.verdict for r in reqs]
want = [model.generate(p[None], max_new_tokens=6, on_device=False)[0].tolist()
        for p in prompts]
assert [list(r.tokens) for r in reqs] == want, \
    'served tokens diverged from model.generate'
assert st['prefix_hit_rate'] > 0, st['prefix_hit_rate']
assert st['chunk_dispatches'] < st['prompt_tokens'], \
    (st['chunk_dispatches'], st['prompt_tokens'])
print('pages shared-prefix smoke OK: bit-identical, hit_rate=%.2f,'
      ' %d dispatches for %d prompt tokens' %
      (st['prefix_hit_rate'], st['chunk_dispatches'], st['prompt_tokens']))
"
    # the paged-attention and arena-write kernels: interpret-mode parity
    # against the XLA references (the only way the kernel CODE runs
    # off-TPU) plus the kernels=off jaxpr-identity contract
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_kernels.py -q -p no:cacheprovider \
        -k "paged_attention or kv_page_write or arena_head_dim or paged_server"
    # the speculative-decoding exactness gate (slow-marked out of the
    # tier-1 sweep for its ~13s drafter drive; covered here every pass)
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_pages.py::test_speculative_bit_identical_to_plain_greedy \
        -q -p no:cacheprovider
}

goodput_stage() {
    echo "== goodput =="
    # goodput must be disabled by default: a full prefetch training loop
    # AND a full serve request lifecycle make ZERO accountant calls
    # (every hook site reduces to one module-bool check), no interval
    # state exists, and nothing is written
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, dataflow, serve, goodput
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.models import gpt as gpt_mod
assert not goodput.enabled(), 'goodput must default to off'
hooks = ('note', 'note_step', 'note_oom_begin', 'note_resume',
         'note_rollback', 'enable')
calls = {h: 0 for h in hooks}
real = {h: getattr(goodput, h) for h in hooks}
for h in hooks:
    setattr(goodput, h, lambda *a, _h=h, **k: calls.__setitem__(_h, calls[_h] + 1))
parallel.make_mesh(dp=-1)
net = nn.Dense(4, in_units=8); mx.random.seed(0); net.initialize()
lfn = gloss.L2Loss()
tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), 'sgd',
                             {'learning_rate': 0.1})
x = nd.array(np.ones((8, 8), np.float32))
y = nd.array(np.zeros((8, 4), np.float32))
for d, l in dataflow.prefetch_to_mesh(iter([([x], [y])] * 3), tr, depth=2):
    tr.step(d, l)
model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
model.initialize()
srv = serve.Server(model, slots=2)
r = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=4)
srv.drain()
srv.stop()
for h in hooks:
    setattr(goodput, h, real[h])
assert r.state == serve.DONE
assert calls == {h: 0 for h in hooks}, calls
assert goodput._totals is None and goodput._cursor is None, \
    'disabled fast path allocated accountant state'
assert goodput.snapshot()['enabled'] is False
print('goodput disabled fast path OK (zero hook calls, no state)')
"
    # seeded-fault acceptance (slow-marked out of the tier-1 sweep):
    # 2-rank launch with --goodput-dir, rank 1 SIGKILLed at step 3,
    # elastic relaunch resumes and replays — tools/goodput_report.py
    # must partition 100% of gang wall-clock (within 1%), attribute the
    # restart downtime, and count replayed steps == high-water minus
    # the restored step; plus the SDC-rollback replay classification
    # and the serve idle/decode split (slow-marked for tier-1 budget,
    # covered here every pass)
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_goodput.py::test_kill_relaunch_report_attributes_downtime_and_replay \
        tests/unittest/test_goodput.py::test_rollback_steps_count_as_replay \
        tests/unittest/test_goodput.py::test_serve_idle_vs_decode_split \
        -q -p no:cacheprovider
}

fleet_stage() {
    echo "== fleet =="
    # fleet=off (the default) must be the zero-overhead production
    # path: a full serve request lifecycle constructs no endpoint, no
    # router, makes zero fleet calls, and the scope status page carries
    # no fleet section — every hook site is one module-bool check
    JAX_PLATFORMS=cpu python -c "
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import fleet, parallel, scope, serve
from mxnet_tpu.models import gpt as gpt_mod
assert not fleet.enabled(), 'fleet must default to off'
hooks = ('snapshot', 'enable', 'ReplicaEndpoint', 'Router')
calls = {h: 0 for h in hooks}
real = {h: getattr(fleet, h) for h in hooks}
for h in hooks:
    setattr(fleet, h, lambda *a, _h=h, **k: (calls.__setitem__(_h, calls[_h] + 1), real[_h](*a, **k))[1])
assert scope._fleet_section() is None, 'fleet=off grew a scope section'
parallel.make_mesh(dp=-1)
model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
mx.random.seed(0); model.initialize()
srv = serve.Server(model, slots=2)
r = srv.submit(np.arange(6, dtype=np.int32), max_new_tokens=4)
srv.drain()
srv.stop()
assert r.state == serve.DONE
assert calls == {h: 0 for h in hooks}, calls
assert scope._fleet_section() is None, 'dense serving armed mx.fleet'
for h in hooks:
    setattr(fleet, h, real[h])
fleet.enable()
sec = scope._fleet_section()
assert sec is not None and 'endpoints' in sec, sec
fleet.disable()
print('fleet disabled fast path OK (no endpoint, no router, no section)')
"
    # kill-a-replica-mid-load acceptance (slow-marked out of the tier-1
    # sweep): tools/launch.py --serve-replicas 2 behind the health
    # router, SIGKILL one replica while a generation streams through
    # it — the stream must complete bit-identically on the survivor
    # (zero accepted requests lost), restarts.jsonl must record the
    # replica_exit + replica_relaunch pair, the relaunched replica must
    # serve again, and SIGTERM must drain both replicas through the
    # resilience preemption path (covered here every pass)
    # plus the rolling-update acceptance (slow-marked out of the tier-1
    # sweep for its ~60s of live replica restarts; covered here every
    # pass): a background client must see every request complete DONE
    # while the fleet rolls replica-by-replica onto a new version
    JAX_PLATFORMS=cpu python -m pytest \
        tests/unittest/test_fleet.py::test_launch_fleet_supervises_replicas \
        tests/unittest/test_fleet.py::test_rolling_update_serves_continuously \
        -q -p no:cacheprovider
}

case "$stage" in
    sanity) sanity ;;
    static) static_stage ;;
    unittest) unittest_stage ;;
    dist) dist_stage ;;
    train) train_stage ;;
    native) native_stage ;;
    pages) pages_stage ;;
    goodput) goodput_stage ;;
    fleet) fleet_stage ;;
    ledger) ledger_stage ;;
    all)
        sanity
        static_stage
        native_stage
        unittest_stage
        dist_stage
        train_stage
        pages_stage
        goodput_stage
        fleet_stage
        ledger_stage
        sh tools/check.sh
        ;;
    *) echo "unknown stage '$stage'" >&2; exit 2 ;;
esac
echo "ci: $stage GREEN"
