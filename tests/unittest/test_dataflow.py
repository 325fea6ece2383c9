"""mx.dataflow: device-side batch prefetch lifecycle, shape bucketing
(bounded executable population + mask-equivalent losses), async step
dispatch (overlap speedup, traced-lr equivalence, periodic fencing),
and the persistent compile-cache wiring."""
import gc
import os
import threading
import time
import traceback

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import dataflow, nd, parallel, telemetry
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "mx-dataflow-prefetch" and t.is_alive()]


@pytest.fixture(autouse=True)
def _no_thread_leak():
    yield
    # every test must shut its prefetch workers down (close/GC/exhaustion)
    deadline = time.time() + 5
    while _prefetch_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert not _prefetch_threads(), "leaked mx-dataflow-prefetch thread"


def _simple_trainer(seed=0):
    parallel.make_mesh(dp=-1)
    mx.random.seed(seed)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    lfn = gloss.L2Loss()
    return parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                                   {"learning_rate": 0.1})


def _xy(seed=0):
    rng = np.random.RandomState(seed)
    return (nd.array(rng.randn(8, 8).astype(np.float32)),
            nd.array(rng.randn(8, 4).astype(np.float32)))


# -- prefetcher lifecycle ---------------------------------------------------

def test_prefetch_drains_in_order_then_stops():
    batches = [([nd.array(np.full((8, 8), i, np.float32))],
                [nd.array(np.zeros((8, 4), np.float32))]) for i in range(12)]
    pf = dataflow.prefetch_to_mesh(iter(batches), None, depth=3)
    seen = [float(d[0].asnumpy()[0, 0]) for d, _ in pf]
    assert seen == [float(i) for i in range(12)]
    with pytest.raises(StopIteration):
        next(pf)
    with pytest.raises(StopIteration):  # exhausted stays exhausted
        next(pf)


def test_partial_iteration_then_gc_leaks_no_threads():
    x, y = _xy()
    pf = dataflow.prefetch_to_mesh(iter([([x], [y])] * 50), None, depth=2)
    next(pf)
    assert _prefetch_threads()          # worker alive mid-iteration
    del pf
    gc.collect()                        # __del__ -> close() -> join
    deadline = time.time() + 5
    while _prefetch_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert not _prefetch_threads()


def test_close_is_idempotent_and_usable_as_context_manager():
    x, y = _xy()
    with dataflow.prefetch_to_mesh(iter([([x], [y])] * 20), None) as pf:
        next(pf)
    pf.close()                          # second close: no-op
    with pytest.raises(StopIteration):
        next(pf)


def test_worker_exception_surfaces_with_original_traceback():
    x, y = _xy()

    def failing_source():
        yield ([x], [y])
        raise ValueError("boom-in-worker")

    pf = dataflow.prefetch_to_mesh(failing_source(), None, depth=2)
    next(pf)
    with pytest.raises(ValueError, match="boom-in-worker") as ei:
        for _ in range(3):
            next(pf)
    # the re-raised exception carries the WORKER's frames, not just ours
    frames = "".join(traceback.format_tb(ei.value.__traceback__))
    assert "failing_source" in frames
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetch_stages_with_trainer_shardings():
    tr = _simple_trainer()
    x, y = _xy()
    pf = dataflow.prefetch_to_mesh(iter([([x], [y])] * 2), tr, depth=2)
    (d, l) = next(pf)
    want = tr._batch_shardings(1, 1, ((8, 8), (8, 4)))
    assert d[0]._data.sharding == want[0]
    assert l[0]._data.sharding == want[1]
    pf.close()


def test_prefetch_losses_bit_exact_vs_unprefetched():
    rng = np.random.RandomState(7)
    raw = [([rng.randn(8, 8).astype(np.float32)],
            [rng.randn(8, 4).astype(np.float32)]) for _ in range(6)]

    tr1 = _simple_trainer(seed=3)
    mx.random.seed(11)
    direct = [float(tr1.step([nd.array(d[0])], [nd.array(l[0])]).asscalar())
              for d, l in raw]

    tr2 = _simple_trainer(seed=3)
    mx.random.seed(11)
    staged = []
    for d, l in dataflow.prefetch_to_mesh(iter(raw), tr2, depth=2):
        staged.append(float(tr2.step_async(d, l).asscalar()))
    assert staged == direct  # bit-exact: staging must not change numerics


# -- shape bucketing --------------------------------------------------------

class MaskedSeqNet(nn.HybridBlock):
    """(B, L, F) varlen input + per-example valid length -> masked mean
    score, so padded positions cannot influence the loss."""

    def __init__(self, features):
        super().__init__()
        self.proj = nn.Dense(1, in_units=features, flatten=False)

    def forward(self, x, valid_len):
        h = self.proj(x)                               # (B, L, 1)
        b, length = x.shape[0], x.shape[1]
        pos = nd.arange(length).reshape((1, length))
        mask = (pos < valid_len.reshape((-1, 1)).astype("float32")) \
            .astype("float32")
        h = h.reshape((b, length)) * mask
        return h.sum(axis=1) / valid_len.astype("float32")


def _masked_trainer():
    parallel.make_mesh(dp=-1)
    mx.random.seed(5)
    net = MaskedSeqNet(6)
    net.initialize()
    lfn = gloss.L2Loss()
    return parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                                   {"learning_rate": 0.05})


def test_bucketpad_bounds_executables_and_matches_unbucketed_losses():
    lengths = [5, 7, 9, 11, 13]        # >= 5 distinct raw lengths
    rng = np.random.RandomState(2)
    xs = [rng.randn(8, L, 6).astype(np.float32) for L in lengths]
    ys = [rng.randn(8).astype(np.float32) for _ in lengths]

    # unbucketed reference: every novel length compiles its own executable
    tr_raw = _masked_trainer()
    mx.random.seed(9)
    raw_losses = []
    for x, y, L in zip(xs, ys, lengths):
        valid = nd.array(np.full(8, L, np.int32))
        raw_losses.append(float(
            tr_raw.step([nd.array(x), valid], [nd.array(y)]).asscalar()))
    assert len(tr_raw._step_cache) == len(lengths)

    # bucketed: 5 raw lengths -> 2 buckets -> <= 2 executables
    bp = dataflow.BucketPad(axis_buckets={1: (8, 16)})
    tr_b = _masked_trainer()
    mx.random.seed(9)
    src = iter([([x], [y]) for x, y in zip(xs, ys)])
    bucketed = []
    for d, l in dataflow.prefetch_to_mesh(src, tr_b, transform=bp):
        assert d[0].shape[1] in (8, 16)
        bucketed.append(float(tr_b.step_async(d, l).asscalar()))
    assert len(tr_b._step_cache) <= 2
    # mask-equivalence: padding must not change the training trajectory
    np.testing.assert_allclose(bucketed, raw_losses, rtol=1e-5, atol=1e-6)


def test_bucketpad_pow2_policy_and_waste_histogram():
    mx.config.set("bucket_pad_min", 8)
    telemetry.reset()
    telemetry.enable()
    try:
        bp = dataflow.BucketPad()      # default: axis 1, pow2 buckets
        x = np.ones((4, 11, 3), np.float32)
        (data, labels) = bp(([x], [np.zeros(4, np.float32)]))
        assert data[0].shape == (4, 16, 3)
        assert data[1].dtype == np.int32 and list(data[1]) == [11] * 4
        assert labels[0].shape == (4,)   # labels untouched below the axis
        h = telemetry.histogram("bucket_pad_waste_ratio")
        assert h.count == 1
        assert h.sum == pytest.approx(1.0 - 11.0 / 16.0)
        # min bucket floors tiny lengths
        (data2, _) = bp(([np.ones((4, 3, 3), np.float32)],
                         [np.zeros(4, np.float32)]))
        assert data2[0].shape == (4, 8, 3)
    finally:
        telemetry.disable()
        telemetry.reset()
        mx.config.reset("bucket_pad_min")


def test_bucketpad_exact_fit_and_oversize():
    bp = dataflow.BucketPad(axis_buckets={1: (8,)})
    (data, _) = bp(([np.ones((2, 8, 3), np.float32)],
                    [np.zeros(2, np.float32)]))
    assert data[0].shape == (2, 8, 3)          # exact fit: no pad
    assert list(data[1]) == [8, 8]             # valid length still emitted
    (data, _) = bp(([np.ones((2, 12, 3), np.float32)],
                    [np.zeros(2, np.float32)]))
    assert data[0].shape == (2, 12, 3)         # above top bucket: raw shape


# -- async dispatch ---------------------------------------------------------

def test_step_async_matches_step_and_advances_device_counter():
    tr = _simple_trainer()
    x, y = _xy()
    l1 = tr.step([x], [y])
    l2 = tr.step_async([x], [y])
    assert np.isfinite(float(l1.asscalar()))
    assert np.isfinite(float(l2.asscalar()))
    assert tr.num_update == 2
    assert float(tr._t_dev) == 2.0     # device counter tracks num_update


def test_overlap_speedup_with_slow_host_iterator():
    """The acceptance gate: an artificially slow host iterator + prefetch
    + async dispatch must beat the serialized (fetch, stage, step, fence)
    loop by >= 1.5x, because host batch production overlaps device
    compute instead of alternating with it."""
    parallel.make_mesh(dp=-1)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(512, activation="relu", in_units=512),
            nn.Dense(512, activation="relu", in_units=512),
            nn.Dense(512, in_units=512))
    net.initialize()
    lfn = gloss.L2Loss()
    tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "sgd",
                                 {"learning_rate": 0.01})
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(64, 512).astype(np.float32))
    y = nd.array(rng.randn(64, 512).astype(np.float32))

    import jax
    jax.block_until_ready(tr.step([x], [y])._data)   # warm the executable
    n = 10

    def measure():
        # calibrate the fenced step time so the synthetic host latency
        # matches device compute: sleep == step is where serialization
        # hurts most (2x theoretical) and overlap shows clearest. Median
        # of 5 so one scheduler blip can't skew the sleep calibration.
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(tr.step([x], [y])._data)
            samples.append(time.perf_counter() - t0)
        step_s = max(sorted(samples)[2], 0.002)

        def slow_source():
            for _ in range(n):
                time.sleep(step_s)      # host batch production
                yield ([x], [y])

        # serialized: host fetch, stage, step, fence — strictly alternating
        t0 = time.perf_counter()
        for d, l in slow_source():
            jax.block_until_ready(tr.step(d, l)._data)
        t_serial = time.perf_counter() - t0

        # overlapped: worker stages while the device computes; async dispatch
        t0 = time.perf_counter()
        for d, l in dataflow.prefetch_to_mesh(slow_source(), tr, depth=2):
            loss = tr.step_async(d, l)
        float(loss.asscalar())          # one fence for the whole window
        t_overlap = time.perf_counter() - t0
        return t_serial / t_overlap, t_serial, t_overlap, step_s

    # timing assert: best of 3 so a noisy-neighbor scheduler blip (CI box
    # under load) can't fail a structurally ~1.8x effect (2n/(n+1))
    results = []
    for _ in range(3):
        results.append(measure())
        if results[-1][0] >= 1.5:
            break
    speedup, t_serial, t_overlap, step_s = max(results)
    assert speedup >= 1.5, (
        f"expected >=1.5x from overlap, got {speedup:.2f}x "
        f"(serial {t_serial:.3f}s, overlapped {t_overlap:.3f}s, "
        f"step {step_s * 1e3:.1f}ms)")


def test_traced_lr_matches_host_lr_for_builtin_schedulers():
    from mxnet_tpu import lr_scheduler as lrs
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel.functional_opt import FunctionalOptimizer
    scheds = [
        None,
        lrs.FactorScheduler(step=10, factor=0.5, base_lr=0.1,
                            warmup_steps=5, warmup_begin_lr=0.01),
        lrs.MultiFactorScheduler(step=[5, 12], factor=0.3, base_lr=0.2),
        lrs.PolyScheduler(max_update=30, base_lr=0.1, pwr=2,
                          final_lr=0.001, warmup_steps=4),
        lrs.CosineScheduler(max_update=25, base_lr=0.05, final_lr=0.005,
                            warmup_steps=3, warmup_mode="exp"),
    ]
    for sch in scheds:
        o = opt_mod.create("adam", learning_rate=0.1)
        o.lr_scheduler = sch
        f = FunctionalOptimizer(o)
        fn = f.lr_traced()
        assert fn is not None, sch
        for t in range(1, 40):
            assert float(fn(np.float32(t))) == pytest.approx(
                f.lr_at(t), abs=1e-7), (type(sch).__name__, t)

    class Custom(lrs.LRScheduler):
        def __call__(self, t):
            return 0.1

    o = opt_mod.create("sgd", learning_rate=0.1)
    o.lr_scheduler = Custom()
    assert FunctionalOptimizer(o).lr_traced() is None


def test_custom_scheduler_falls_back_to_host_lr():
    from mxnet_tpu import lr_scheduler as lrs

    class Halving(lrs.LRScheduler):
        def __call__(self, t):
            return 0.1 if t < 3 else 0.05

    parallel.make_mesh(dp=-1)
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    lfn = gloss.L2Loss()
    tr = parallel.ShardedTrainer(
        net, lambda o, l: lfn(o, l), "sgd",
        {"learning_rate": 0.1, "lr_scheduler": Halving()})
    assert not tr._lr_inside
    x, y = _xy()
    losses = [float(tr.step([x], [y]).asscalar()) for _ in range(4)]
    assert all(np.isfinite(v) for v in losses)
    assert losses == sorted(losses, reverse=True)  # still optimizing


def test_constant_lr_change_rejits_instead_of_stale_rate():
    tr = _simple_trainer()
    x, y = _xy()
    tr.step([x], [y])
    assert len(tr._step_cache) == 1
    tr._opt.set_learning_rate(0.2)
    tr.step([x], [y])
    # new executable keyed on the new constant lr — one warm re-jit, the
    # updated rate applies, and the stale rate's executable is evicted
    # (a set_learning_rate loop must not leak one executable per value)
    assert len(tr._step_cache) == 1
    assert all(k[3] == 0.2 for k in tr._step_cache)


def test_scheduler_field_mutation_rejits():
    from mxnet_tpu.lr_scheduler import PolyScheduler
    parallel.make_mesh(dp=-1)
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    lfn = gloss.L2Loss()
    tr = parallel.ShardedTrainer(
        net, lambda o, l: lfn(o, l), "sgd",
        {"learning_rate": 0.1,
         "lr_scheduler": PolyScheduler(100, base_lr=0.1)})
    assert tr._lr_inside
    x, y = _xy()
    tr.step([x], [y])
    key0 = next(iter(tr._step_cache))
    # editing the live scheduler re-keys the executable (the old host-lr
    # path re-read the scheduler every step; baking it in-jit must not
    # silently pin the stale hyperparameters)
    tr._opt.lr_scheduler.base_lr = 0.01
    tr.step([x], [y])
    assert len(tr._step_cache) == 1     # stale entry evicted
    assert next(iter(tr._step_cache)) != key0


def test_step_failure_keeps_counters_in_sync():
    tr = _simple_trainer()
    x, y = _xy()
    tr.step([x], [y])
    bad = nd.array(np.ones((8, 5), np.float32))  # wrong feature width
    with pytest.raises(Exception):
        tr.step([bad], [y])             # trace-time shape error
    # the failed step must not advance the host counter past the
    # device-resident one
    assert tr.num_update == 1
    assert float(tr._t_dev) == 1.0
    tr.step([x], [y])
    assert tr.num_update == 2 and float(tr._t_dev) == 2.0


def test_fence_every_knob_controls_sync_step_fencing():
    import jax
    tr = _simple_trainer()
    x, y = _xy()
    tr.step([x], [y])                   # compile outside counted window
    fences = []
    real = jax.block_until_ready
    jax.block_until_ready = lambda v: (fences.append(1), real(v))[1]
    try:
        mx.config.set("trainer_async_fence_every", 2)
        for _ in range(4):
            tr.step([x], [y])
        assert len(fences) == 2         # steps 2 and 4 (num_update 3, 5... every 2)
        fences.clear()
        for _ in range(4):
            tr.step_async([x], [y])     # async API never self-fences
        assert fences == []
        mx.config.set("trainer_async_fence_every", 0)
        for _ in range(4):
            tr.step([x], [y])
        assert fences == []             # default: fence-free sync path too
        # diagnostics-only mode records without fencing — the knob's
        # periodic fence must still apply there
        from mxnet_tpu import diagnostics
        mx.config.set("trainer_async_fence_every", 2)
        diagnostics.enable()
        try:
            for _ in range(4):
                tr.step([x], [y])
        finally:
            diagnostics.disable()
            diagnostics.reset()
        assert len(fences) == 2
    finally:
        jax.block_until_ready = real
        mx.config.reset("trainer_async_fence_every")


def test_checkpoint_restores_device_step_counter(tmp_path):
    tr = _simple_trainer(seed=4)
    x, y = _xy()
    for _ in range(3):
        tr.step([x], [y])
    tr.save_states(str(tmp_path / "ck"))
    cont = float(tr.step([x], [y]).asscalar())

    tr2 = _simple_trainer(seed=4)
    tr2.load_states(str(tmp_path / "ck"))
    assert tr2.num_update == 3
    assert float(tr2._t_dev) == 3.0
    resumed = float(tr2.step([x], [y]).asscalar())
    assert resumed == cont              # trajectory-exact resume


# -- telemetry ---------------------------------------------------------------

def test_prefetch_telemetry_series():
    telemetry.reset()
    telemetry.enable()
    try:
        tr = _simple_trainer()
        rng = np.random.RandomState(0)
        src = iter([([rng.randn(8, 8).astype(np.float32)],
                     [rng.randn(8, 4).astype(np.float32)])
                    for _ in range(4)])
        for d, l in dataflow.prefetch_to_mesh(src, tr, depth=2):
            tr.step_async(d, l)
        assert telemetry.counter("h2d_bytes_total").value \
            == 4 * (8 * 8 + 8 * 4) * 4
        assert telemetry.histogram("device_prefetch_wait_seconds").count == 4
        depth = telemetry.gauge("dataloader_prefetch_depth")
        assert ("stage", "device") in {k for key in depth._children
                                       for k in key}
    finally:
        telemetry.disable()
        telemetry.reset()


def test_host_and_device_depth_are_distinct_series():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    telemetry.reset()
    telemetry.enable()
    try:
        ds = ArrayDataset(
            nd.array(np.arange(64, dtype=np.float32).reshape(16, 4)),
            nd.array(np.arange(16, dtype=np.float32)))
        loader = DataLoader(ds, batch_size=4, num_workers=2,
                            thread_pool=True)
        for d, l in dataflow.prefetch_to_mesh(iter(loader), None, depth=2):
            pass
        depth = telemetry.gauge("dataloader_prefetch_depth")
        stages = {dict(key).get("stage") for key in depth._children}
        assert {"host", "device"} <= stages
    finally:
        telemetry.disable()
        telemetry.reset()


def test_telemetry_report_names_bottleneck_stage(tmp_path):
    import json
    import os
    import subprocess
    import sys
    telemetry.reset()
    telemetry.enable()
    try:
        telemetry.histogram("dataloader_wait_seconds").observe(0.3)
        telemetry.histogram("device_prefetch_wait_seconds").observe(0.1)
        telemetry.histogram("trainer_step_seconds").observe(0.2)
        telemetry.counter("compile_cache_hits_total").inc(3)
        telemetry.counter("compile_cache_misses_total").inc(1)
        path = str(tmp_path / "run.jsonl")
        telemetry.dump_jsonl(path)
    finally:
        telemetry.disable()
        telemetry.reset()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "telemetry_report.py"),
         path], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "bottleneck stage: host batch production" in r.stdout
    assert "host batch 0.30s (overlapped)" in r.stdout
    assert "persistent cache: 3 warm hits, 1 cold misses" in r.stdout
    # consumer stall = staging wait only (host wait overlaps in the
    # prefetch worker): 0.1 / (0.1 + 0.2)
    assert "stall fraction 33.3%" in r.stdout


# -- estimator integration ---------------------------------------------------

def test_estimator_drives_prefetcher_for_dataloader():
    from mxnet_tpu import metric
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    mx.random.seed(0)
    net = nn.Dense(2, in_units=4)
    net.initialize()
    ds = ArrayDataset(
        nd.array(np.random.RandomState(0).randn(16, 4).astype(np.float32)),
        nd.array(np.random.RandomState(1).randint(0, 2, 16)
                 .astype(np.float32)))
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(),
                    train_metrics=[metric.Loss("loss")],
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1})
    it, closer = est._epoch_iter(DataLoader(ds, batch_size=4))
    assert isinstance(it, dataflow.MeshPrefetcher)
    closer()
    est.fit(DataLoader(ds, batch_size=4), epochs=2)
    assert est.num_batch == 8
    # knob off: the plain iterator comes back
    mx.config.set("device_prefetch_depth", 0)
    try:
        it, closer = est._epoch_iter(DataLoader(ds, batch_size=4))
        assert not isinstance(it, dataflow.MeshPrefetcher)
        closer()
    finally:
        mx.config.reset("device_prefetch_depth")


# -- persistent compile cache ------------------------------------------------

@pytest.fixture
def fresh_cache_decision(monkeypatch):
    """ensure_compile_cache decides once per process; give the test a
    clean slate and put jax's directory back afterwards."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(dataflow, "_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_ensure_compile_cache_defaults_to_fixed_checkout_path(
        fresh_cache_decision, monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(dataflow.__file__)))
    got = dataflow.ensure_compile_cache()
    # fixed, inside the checkout: never a temporary or per-process path
    assert got == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert dataflow.ensure_compile_cache() == got   # idempotent


def test_ensure_compile_cache_leaves_env_directory_alone(
        fresh_cache_decision, monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, the directory is jax's to
    read from the environment: the repo must not update it in code."""
    import jax
    outside = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val))[1])
    assert dataflow.ensure_compile_cache() == outside
    assert "jax_compilation_cache_dir" not in updates
    assert dataflow.ensure_compile_cache() == outside   # idempotent
