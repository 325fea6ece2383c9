"""ShardedTrainer: one jitted, mesh-sharded train step.

This is the TPU-native performance path the reference cannot express: where
the reference runs eager-op forward, tape backward, then per-parameter
kvstore push/pull + update ops (`gluon/trainer.py` step → `src/kvstore/*`),
here the ENTIRE step — forward, loss, backward, gradient reduction (XLA psum
over the data axes), optimizer — is one XLA computation over a named mesh.
Parameters/optimizer state live device-resident and donated between steps;
gradient reduction rides ICI; fsdp mode shards params + optimizer state
(weight-update sharding).

Gluon blocks plug in unchanged via `gluon.functional_call`.
"""
from __future__ import annotations

import contextlib
import math
import time

import jax
import jax.numpy as jnp

from .. import random as _random
from .. import _engine
from .. import check as _check
from .. import config as _config
from .. import diagnostics as _diagnostics
from .. import goodput as _goodput
from .. import guard as _guard
from .. import inspect as _inspect
from .. import memsafe as _memsafe
from .. import resilience as _resilience
from .. import scope as _scope
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..gluon.block import functional_call
from ..ndarray import NDArray
from . import specs as _specs
from . import zero as _zero
from .functional_opt import FunctionalOptimizer
from .mesh import current_mesh

__all__ = ["ShardedTrainer", "call_loss"]

# reusable do-nothing context for the unsampled/disabled trace path (a
# fresh nullcontext per step would be an allocation on the hot path)
_NULLCTX = contextlib.nullcontext()

# shared, framework-wide series (get-or-create: same objects as the
# HybridBlock jit cache and the gluon Trainer register)
_M_COMPILES = _telemetry.counter("compile_total")
_M_RECOMPILES = _telemetry.counter("recompile_total")
_M_COMPILE_SECONDS = _telemetry.histogram("compile_seconds")
_M_STEP_SECONDS = _telemetry.histogram("trainer_step_seconds")
_M_COLL_CALLS = _telemetry.counter(
    "collective_calls_total", "XLA collectives issued per jitted train step "
    "(host-side accounting: the gradient psum on the data axes — or, on a "
    "mx.zero'd trainer, the gradient reduce-scatter + updated-param "
    "all-gather pair)")
_M_COLL_BYTES = _telemetry.counter(
    "collective_bytes_total", "payload bytes moved by the counted "
    "collectives (gradient/param bytes per reducing step, labeled by op)")


def call_loss(loss_fn, rng, outs, labels):
    """Invoke a user loss_fn on raw arrays inside a traced train step:
    recording off, training mode on, loss RNG pinned to fold_in(rng, 1).
    Shared by ShardedTrainer and PipelineTrainer so the engine-flag and
    rng conventions cannot drift between them."""
    prev_r = _engine.set_recording(False)
    prev_t = _engine.set_training(True)
    try:
        with _random.key_scope(jax.random.fold_in(rng, 1)):
            loss_nd = loss_fn(*[NDArray(o) for o in outs],
                              *[NDArray(l) for l in labels])
    finally:
        _engine.set_recording(prev_r)
        _engine.set_training(prev_t)
    return jnp.mean(loss_nd._data.astype(jnp.float32))


def _batch_arrays(data, labels):
    """(data list, labels list, their raw jax arrays in that order) from
    what `step` accepts: an NDArray/array or a list of them for each."""
    data = data if isinstance(data, (list, tuple)) else [data]
    labels = labels if isinstance(labels, (list, tuple)) else [labels]
    return data, labels, [
        b._data if isinstance(b, NDArray) else jnp.asarray(b)
        for b in list(data) + list(labels)]


class ShardedTrainer:
    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, param_mode="replicate", donate=True,
                 data_specs=None, label_specs=None):
        """data_specs/label_specs: optional per-array PartitionSpec overrides
        for the batch inputs (None entries fall back to the default
        batch-on-data-axes spec) — e.g. P(('dp','fsdp'), 'sp') to shard
        token sequences for long-context/ring-attention training."""
        from .. import optimizer as opt_mod
        self.block = block
        self.loss_fn = loss_fn
        self.mesh = mesh or current_mesh()
        self.param_mode = param_mode
        self._data_specs = list(data_specs) if data_specs else []
        self._label_specs = list(label_specs) if label_specs else []
        self._opt = opt_mod.create(optimizer, **(optimizer_params or {})) \
            if isinstance(optimizer, str) else optimizer
        self._donate = donate
        self.num_update = 0
        self._step_cache = {}
        self._ready = False
        self._tele_sig = None
        self._tele_reduce_bytes = 0
        self._tele_coll = {}
        self._coll_est = {}
        self._zero = False
        self._zero_specs = None
        self._zero_flat = None
        # gradient-accumulation factor (mx.memsafe degradation ladder /
        # set_grad_accum): the jitted step splits the global batch into
        # this many microbatches, accumulating grads — loss/grad parity
        # with the full batch up to reduction order
        self._accum = 1
        # arm memsafe/check iff their knobs ask (oom_recover=auto /
        # device_bytes_limit / check!=off): construction-time config
        # reads only — the step hot path keeps its single module-bool
        # check per subsystem
        _memsafe.maybe_enable()
        _check.maybe_enable()
        _guard.maybe_enable()
        _scope.maybe_enable()
        # persistent XLA compilation cache: turned on once, at first
        # trainer construction, before anything compiles
        from .. import dataflow as _dataflow
        _dataflow.ensure_compile_cache()
        from ..gluon.parameter import DeferredInitializationError
        try:
            self._setup()
        except DeferredInitializationError:
            # deferred parameter shapes: resolved by an eager probe pass on
            # the first step's batch (reference: deferred init on forward)
            pass
        if _resilience._enabled:
            # auto-resume per the `resume` knob: restore params/optimizer/
            # RNG/device-step-counter from the newest VERIFIED checkpoint
            # before any step runs (one module-bool check when disabled)
            _resilience.on_trainer_init(self)

    def _setup(self):
        self._fn, self._grad_params, self._aux_params = functional_call(
            self.block, train=True)
        self._names = [name for name, _ in self._grad_params]
        self.fopt = FunctionalOptimizer(self._opt, self._names)

        # shardings
        self._pshard = [
            _specs.param_spec(p, self.mesh, self.param_mode)
            for _, p in self._grad_params]
        self._aux_shard = [_specs.replicated(self.mesh) for _ in self._aux_params]
        rep = _specs.replicated(self.mesh)
        self._rep = rep

        # Fused multi-tensor LAMB + f32 flat master weights (reference
        # multi_mp_lamb_update): replicate mode only — under fsdp/tp the
        # per-parameter path shards cleanly, the flat concat would not.
        from .. import config
        self._fused = (
            self.fopt.kind == "lamb" and self.param_mode == "replicate"
            and config.get("fused_lamb"))
        # mx.zero: shard optimizer state (fused-LAMB masters included)
        # across the data axes per the `zero` knob. With the knob off
        # (default) this whole region is one module-bool check — no call
        # into the zero module at all (ci/run.sh sanity asserts it)
        self._zero = False
        self._zero_specs = None       # per-param opt-state shardings
        self._zero_flat = None        # fused flat master/moment sharding
        _zero.maybe_enable()
        zero_want = _zero._enabled and _config.get("zero") != "off"
        if self._fused:
            from .fused_lamb import FusedLamb
            o = self.fopt.opt
            datas = [p.data()._data for _, p in self._grad_params]
            self._fl = FusedLamb(
                [d.shape for d in datas], [d.dtype for d in datas],
                [self.fopt._wd_for(i) for i in range(len(datas))],
                o.beta1, o.beta2, o.epsilon, o.bias_correction,
                o.rescale_grad, o.clip_gradient or -1.0,
                o.lower_bound or -1.0, o.upper_bound or -1.0,
                moments_dtype=config.get("lamb_moments_dtype"))
            if zero_want:
                self._zero_flat = _zero.flat_spec(self._fl, self.mesh)
                self._zero = self._zero_flat is not None
            master = self._fl.flatten(datas)
            pspec = self._zero_flat if self._zero else rep
            self.params = jax.device_put(master, pspec)
            mdt = self._fl.moments_dtype
            self.opt_state = (
                jax.device_put(jnp.zeros(master.shape, mdt), pspec),
                jax.device_put(jnp.zeros(master.shape, mdt), pspec))
        else:
            self.params = [jax.device_put(p.data()._data, s)
                           for (_, p), s in zip(self._grad_params, self._pshard)]
            # optimizer state shards like its parameter (weight-update
            # sharding) — under mx.zero, additionally across the free
            # data axes (reduce-scatter/all-gather weight update)
            states = self.fopt.init(self.params)
            if zero_want:
                self._zero_specs = _zero.plan_state(
                    self.params, self._pshard, states, self.mesh)
                self._zero = any(s is not None for s in self._zero_specs)
                if not self._zero:
                    self._zero_specs = None
            self.opt_state = [
                tuple(jax.device_put(z, zs or s) for z in st)
                for st, zs, s in zip(
                    states,
                    self._zero_specs or [None] * len(states),
                    self._pshard)]
        if zero_want and not self._zero and _config.get("zero") == "on":
            raise ValueError(
                "zero='on' but nothing can shard: the mesh's data axes "
                f"span {_zero.data_extent(self.mesh)} device(s) and/or no "
                "optimizer-state buffer clears zero_min_size with a "
                "divisible dim. Use zero='auto' to no-op silently.")
        self.aux = [jax.device_put(p.data()._data, s)
                    for (_, p), s in zip(self._aux_params, self._aux_shard)]
        # the step counter lives ON DEVICE, incremented inside the jitted
        # step and donated like the rest of the train state: the hot path
        # then ships zero per-step scalars (the old host-side t/lr pair
        # cost two H2D transfers per step). int32 so `t + 1` stays exact
        # past 2^24 steps (a float32 counter would silently freeze there,
        # and with it the lr schedule and bias correction). When the lr
        # schedule is traceable (lr_traced), lr is computed from it inside
        # the step too; otherwise lr falls back to a host-computed traced
        # argument.
        self._t_dev = jax.device_put(
            jnp.asarray(self.num_update, jnp.int32), rep)
        self._lr_inside = self.fopt.lr_traced() is not None
        self._refresh_comm_estimates()
        self._ready = True

    def _refresh_comm_estimates(self):
        """Mesh-derived accounting for the CURRENT mesh + shardings:
        gradient-reduction payload for the collective counters and the
        mx.inspect per-collective traffic estimate. Called from _setup
        and again after an elastic resize or set_zero changes the
        layout."""
        # gradient-reduction payload per step, for the collective counters:
        # XLA psums grads over the data axes iff they span >1 device; a
        # mx.zero'd param instead reduce-scatters its gradient and
        # all-gathers its updated value (same payload, different ops)
        reduce_degree = self.mesh.shape.get("dp", 1) * \
            self.mesh.shape.get("fsdp", 1)
        if self._fused:
            nbytes = int(self.params.size * self.params.dtype.itemsize)
            entries = [(nbytes, self._rep, self._zero)]
        else:
            zflags = self._zero_specs or [None] * len(self.params)
            entries = [(int(p.size * p.dtype.itemsize), s, zs is not None)
                       for p, s, zs in zip(self.params, self._pshard,
                                           zflags)]
        psum_b = rs_b = ag_b = 0
        if reduce_degree > 1:
            for nbytes, _s, z in entries:
                if z:
                    rs_b += nbytes
                    ag_b += nbytes
                else:
                    psum_b += nbytes
        self._tele_reduce_bytes = psum_b + rs_b
        self._tele_coll = {op: n for op, n in (
            ("psum_grad", psum_b), ("reduce_scatter_grad", rs_b),
            ("all_gather_param", ag_b)) if n}
        # per-collective traffic estimate (mx.inspect): bytes each step's
        # gradient reduction / fsdp gather-scatter / zero reduce-scatter+
        # all-gather moves, from the specs just chosen + mesh shape.
        # One-time host arithmetic at setup
        self._coll_est = _inspect.estimate_collectives(
            self.mesh, [(n, s) for n, s, _z in entries],
            zero=[z for _n, _s, z in entries])

    # ------------------------------------------------------------------
    def _build_step(self, n_data, n_label, batch_shapes):
        fn = self._fn
        loss_fn = self.loss_fn
        fopt = self.fopt
        fused = self._fused
        fl = self._fl if fused else None
        # mx.zero: the sharded-update wiring is baked into the step at
        # build time (set_zero clears the step cache); with zero off all
        # three stay None/empty and the step body is byte-identical to
        # the classic path
        zflat = self._zero_flat if (self._zero and fused) else None
        zspecs = self._zero_specs if (self._zero and not fused) else None
        pshard_l = self._pshard if not fused else None
        rep_sh = self._rep
        accum = int(self._accum)
        if accum > 1:
            for shape in batch_shapes:
                if not shape or shape[0] % accum:
                    raise ValueError(
                        f"grad accumulation x{accum}: every batch/label "
                        f"array needs a leading dim divisible by {accum}, "
                        f"got shape {shape}")
        # re-snapshotted per build: a constant-lr schedule bakes the
        # CURRENT o.lr into the executable (the step-cache key carries the
        # value, so set_learning_rate costs one warm re-jit, not a
        # per-step transfer)
        lr_fn = self.fopt.lr_traced() if self._lr_inside else None

        def step(params, aux, opt_state, t, *rest):
            if lr_fn is None:
                lr, rng = rest[0], rest[1]
                batch = rest[2:]
            else:
                rng = rest[0]
                batch = rest[1:]
            t = t + 1            # device-resident num_update (int32: exact)
            tf = t.astype(jnp.float32)
            if lr_fn is not None:
                lr = lr_fn(tf)
            data, labels = batch[:n_data], batch[n_data:]

            def loss_of(ps, aux_in, data, labels, rng):
                # named scopes: a device trace knows the step's fusions,
                # copies and pads by instruction number only; under
                # value_and_grad the backward pass shows up as
                # `transpose(jvp(forward))` (mx.trace.scope_map reads
                # the names back from the executable)
                with jax.named_scope("forward"):
                    if fused:
                        # per-tensor model-dtype views of the flat f32
                        # master; the vjp of this unflatten returns the
                        # gradient FLAT
                        ps = fl.unflatten(ps)
                    outs, new_aux = fn(ps, aux_in, rng, *data)
                    loss = call_loss(loss_fn, rng, outs, labels)
                return loss, new_aux

            fwd_params = params
            if zflat is not None:
                # zero'd fused LAMB: the RESIDENT master is sharded; the
                # forward needs the whole vector, so gather it once here
                # (in-jit — XLA overlaps the all-gather with whatever
                # else is ready). Gradients are taken wrt this gathered
                # value, then reduce-SCATTERED below instead of psum'd.
                fwd_params = _zero.constrain(params, rep_sh)

            if accum <= 1:
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(fwd_params, aux, data, labels,
                                           rng)
            else:
                # gradient-accumulation microbatching (mx.memsafe
                # degradation ladder): lax.scan over `accum` equal slices
                # of the batch, summing grads — activation memory is one
                # microbatch's, and mean-of-means == full-batch mean for
                # equal chunks, so loss/grad match the unsplit step up to
                # reduction order. Each microbatch folds its index into
                # the step rng so dropout draws stay distinct, and aux
                # state (BatchNorm running stats) CHAINS through the scan
                # carry so every microbatch's update lands, not just the
                # last one's.
                split = [b.reshape((accum, b.shape[0] // accum)
                                   + b.shape[1:]) for b in batch]

                def micro(carry, xs):
                    g_acc, l_acc, aux_c = carry
                    i, mb = xs[0], list(xs[1:])
                    (l, na), g = jax.value_and_grad(
                        loss_of, has_aux=True)(
                            fwd_params, aux_c, mb[:n_data], mb[n_data:],
                            jax.random.fold_in(rng, i))
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l, na), None

                g0 = jax.tree.map(jnp.zeros_like, fwd_params)
                (g_sum, l_sum, new_aux), _ = jax.lax.scan(
                    micro, (g0, jnp.zeros((), jnp.float32), list(aux)),
                    (jnp.arange(accum),) + tuple(split))
                grads = jax.tree.map(lambda g: g / accum, g_sum)
                loss = l_sum / accum
            if fused:
                if zflat is not None:
                    # reduce-scatter the flat gradient: each device lands
                    # the shard matching its resident master/moments
                    with jax.named_scope("grad_reduce"):
                        grads = _zero.constrain(grads, zflat)
                with jax.named_scope("optimizer"):
                    new_params, new_m, new_v = fl.apply_flat(
                        params, grads, opt_state[0], opt_state[1], tf, lr)
                new_opt = (new_m, new_v)
            elif zspecs is not None:
                # mx.zero weight-update sharding (arxiv 2004.13336):
                # reduce-scatter each zero'd gradient, slice the matching
                # param shard (free — a sharding constraint, no movement),
                # run the optimizer on 1/D of the elements, then
                # all-gather the updated param back to its resident
                # layout. XLA emits the collectives from the constraints
                # and can overlap the all-gather with the tail of
                # backward; non-zero'd params (tiny state) keep the psum
                with jax.named_scope("grad_reduce"):
                    grads = [g if zs is None else _zero.constrain(g, zs)
                             for g, zs in zip(grads, zspecs)]
                with jax.named_scope("optimizer"):
                    w_upd = [p if zs is None else _zero.constrain(p, zs)
                             for p, zs in zip(params, zspecs)]
                    new_params, new_opt = fopt.apply(w_upd, grads,
                                                     opt_state, tf, lr)
                    new_params = [w if zs is None
                                  else _zero.constrain(w, ps)
                                  for w, zs, ps in zip(new_params, zspecs,
                                                       pshard_l)]
            else:
                with jax.named_scope("optimizer"):
                    new_params, new_opt = fopt.apply(params, grads,
                                                     opt_state, tf, lr)
            return loss, new_params, new_aux, new_opt, t

        donate = (0, 1, 2, 3) if self._donate else (3,)
        if fused:
            pshard = zflat if zflat is not None else self._rep
            oshard = (pshard, pshard)
        else:
            pshard = self._pshard
            # zero'd opt state goes in AND comes out in its sharded
            # layout — identical avals + shardings, so donation aliases
            # cleanly (no double-buffering; mx.check stays quiet)
            zs_l = zspecs or [None] * len(self.opt_state)
            oshard = [tuple((zs or s) for _ in st)
                      for st, zs, s in zip(self.opt_state, zs_l,
                                           self._pshard)]
        scalar_in = () if lr_fn is not None else (self._rep,)
        in_shardings = (
            pshard, self._aux_shard, oshard, self._rep,
        ) + scalar_in + (self._rep,) \
            + tuple(self._batch_shardings(n_data, n_label, batch_shapes))
        out_shardings = (self._rep, pshard, self._aux_shard, oshard,
                         self._rep)
        return jax.jit(step, donate_argnums=donate,
                       in_shardings=in_shardings, out_shardings=out_shardings)

    # ------------------------------------------------------------------
    def _batch_shardings(self, n_data, n_label, shapes):
        from jax.sharding import NamedSharding

        overrides = (self._data_specs + [None] * n_data)[:n_data] + \
            (self._label_specs + [None] * n_label)[:n_label]
        return [NamedSharding(self.mesh, ov) if ov is not None
                else _specs.batch_spec(len(shape), self.mesh)
                for ov, shape in zip(overrides, shapes)]

    # ------------------------------------------------------------------
    def step(self, data, labels):
        """Run one train step. data/labels: NDArray or list of NDArrays
        (global batch; sharded onto the mesh's data axes here — batches
        already staged by dataflow.prefetch_to_mesh skip the transfer).
        Dispatch is asynchronous: the returned loss is lazy, and with
        telemetry/diagnostics/nan_sentinel disabled this path performs no
        host fence and no scalar device transfers. The
        `trainer_async_fence_every` knob adds a periodic host fence
        (every N steps) to bound dispatch run-ahead."""
        fence_every = _config.get("trainer_async_fence_every")
        return self._step_impl(data, labels, fence_every)

    def step_async(self, data, labels):
        """`step` minus the periodic fence: pure async dispatch returning
        a lazy loss handle. Nothing blocks until an explicit
        `.asscalar()`/`.item()`/`asnumpy()` on the handle (or telemetry/
        nan_sentinel, which document that they fence). Use with
        `dataflow.prefetch_to_mesh` so neither H2D transfer nor host
        bookkeeping sits between consecutive device steps."""
        return self._step_impl(data, labels, 0)

    def lower_step(self, data, labels):
        """The `jax.stages.Lowered` form of the executable that
        `step(data, labels)` runs, at the trainer's current state — for
        ahead-of-time checks (which Pallas kernels the step holds, whether
        it compiles for a given chip: chip_smoke.py and tools/aot_check.py
        read it). Dispatches nothing and leaves the RNG stream where it
        was."""
        if not self._ready:
            raise RuntimeError(
                "lower_step needs materialized parameters — run one step "
                "(or construct with explicit shapes) first")
        data, labels, batch = _batch_arrays(data, labels)
        shapes = tuple(b.shape for b in batch)
        scalars = () if self._lr_inside else (jnp.asarray(
            self.fopt.lr_at(self.num_update + 1), jnp.float32),)
        batch = [jax.device_put(b, s) for b, s in zip(
            batch, self._batch_shardings(len(data), len(labels), shapes))]
        return self._build_step(len(data), len(labels), shapes).lower(
            self.params, self.aux, self.opt_state, self._t_dev, *scalars,
            _random.get_state(), *batch)

    def set_grad_accum(self, accum):
        """Set the gradient-accumulation factor: the jitted step splits
        the global batch into `accum` equal microbatches (lax.scan),
        accumulating gradients, so activation memory scales with the
        MICRObatch while loss/grads match the unsplit step up to
        reduction order. Every batch/label leading dim must divide by
        `accum` (validated at the next build). The mx.memsafe
        oom_recover=auto ladder drives this automatically."""
        accum = int(accum)
        if accum < 1:
            raise ValueError(f"grad accumulation factor must be >= 1, "
                             f"got {accum}")
        self._accum = accum
        self._step_cache.clear()
        return self

    def set_zero(self, on=True):
        """Toggle mx.zero optimizer-state sharding on a LIVE trainer:
        the resident moments (and fused-LAMB flat master) re-place into
        the sharded layout across the mesh's free data axes, and the
        next step re-jits with the reduce-scatter -> per-shard update ->
        all-gather wiring (off: everything moves back to the parameter's
        own sharding and the classic psum step). Values are bit-identical
        either way — only the layout moves. The mx.memsafe
        oom_recover=auto ladder drives this as the rung between
        remat='full' and gradient accumulation; zero='auto'/'on' does it
        at construction. Raises ValueError when nothing can shard."""
        if not self._ready:
            raise RuntimeError(
                "set_zero needs materialized parameters — run one step "
                "(or construct with explicit shapes) first")
        on = bool(on)
        if on == bool(self._zero):
            return self
        if on:
            _zero.enable()     # arm the module for the re-jitted step
            if self._fused:
                spec = _zero.flat_spec(self._fl, self.mesh)
                if spec is None:
                    raise ValueError(
                        "mx.zero: the fused-LAMB flat layout cannot "
                        "shard on this mesh (no data axis spans >1 "
                        "device, or rows do not divide)")
                self._zero_flat = spec
                self.params = jax.device_put(self.params, spec)
                self.opt_state = tuple(jax.device_put(z, spec)
                                       for z in self.opt_state)
            else:
                specs = _zero.plan_state(self.params, self._pshard,
                                         self.opt_state, self.mesh)
                if not any(s is not None for s in specs):
                    raise ValueError(
                        "mx.zero: no optimizer-state buffer can shard on "
                        "this mesh (no free data axis spans >1 device, "
                        "or everything is under zero_min_size)")
                self._zero_specs = specs
                self.opt_state = [
                    tuple(jax.device_put(z, zs or s) for z in st)
                    for st, zs, s in zip(self.opt_state, specs,
                                         self._pshard)]
            self._zero = True
        else:
            if self._fused:
                self.params = jax.device_put(self.params, self._rep)
                self.opt_state = tuple(jax.device_put(z, self._rep)
                                       for z in self.opt_state)
                self._zero_flat = None
            else:
                self.opt_state = [
                    tuple(jax.device_put(z, s) for z in st)
                    for st, s in zip(self.opt_state, self._pshard)]
                self._zero_specs = None
            self._zero = False
        self._step_cache.clear()
        self._refresh_comm_estimates()
        return self

    def _lr_cache_key(self):
        """The step-cache component for everything the in-jit lr bakes
        into the executable: None when lr is a traced argument (host
        fallback — nothing baked), the current lr for constant schedules,
        or the built-in scheduler's hyperparameter values. Mid-run
        mutation (set_learning_rate, editing scheduler fields) then
        re-jits warm instead of silently training at the stale schedule;
        the eviction in _step_impl bounds the cache at one entry per
        shape."""
        if not self._lr_inside:
            return None
        sch = self._opt.lr_scheduler
        if sch is None:
            return float(self._opt.lr)
        return (type(sch).__name__,) + tuple(
            (k, tuple(v) if isinstance(v, (list, tuple)) else v)
            for k, v in sorted(vars(sch).items())
            if isinstance(v, (int, float, str, list, tuple)))

    def _step_impl(self, data, labels, fence_every):
        # mx.trace: read once a step; every span site tests this local
        tr = _trace.live()
        try:
            with (_trace.span("train.step", cat="phase",
                              step=self.num_update + 1)
                  if tr else _NULLCTX):
                return self._step_once(data, labels, fence_every, tr)
        except Exception as e:  # noqa: BLE001 — classified below
            # mx.memsafe graceful OOM degradation: RESOURCE_EXHAUSTED and
            # pre-flight MemoryBudgetError walk the ladder under
            # oom_recover=auto. Disabled (default): one module-bool read
            # on an already-failing path, then re-raise — nothing on the
            # success hot path at all (zero-cost try in py3.11+)
            if not _memsafe._enabled or not _memsafe.is_oom(e):
                raise
            return _memsafe.recover_trainer(self, e, data, labels,
                                            fence_every)

    def _step_once(self, data, labels, fence_every, tr=False):
        """One step. `tr` is the caller's `mx.trace.live()` reading for
        this step (memsafe's retry after an OOM runs untraced)."""
        data, labels, batch = _batch_arrays(data, labels)
        if not self._ready:
            with jax.default_device(jax.devices()[0]):
                prev = _engine.set_recording(False)
                try:
                    self.block(*data)  # eager probe resolves deferred shapes
                finally:
                    _engine.set_recording(prev)
            self._setup()
        shapes = tuple(b.shape for b in batch)
        # memsafe extras in the key: the grad-accum factor, the block's
        # remat epoch (bumped by every remat() call — one int attr read,
        # so a mid-run policy change re-jits with memsafe off too), and
        # (enabled only — the disabled path adds no block walk) the
        # effective policy string, so a ladder escalation or a knob-driven
        # default change can never reuse the pre-escalation executable
        pol = _memsafe.policy_marker(self.block) if _memsafe._enabled \
            else None
        key = (len(data), len(labels), shapes, self._lr_cache_key(),
               self._accum, getattr(self.block, "_remat_epoch", 0), pol)
        is_miss = key not in self._step_cache
        # committed only AFTER the jitted call returns, so a trace-time
        # error or failed dispatch can't desync the host counter from the
        # device-resident _t_dev (which only advances on a completed call)
        step_no = self.num_update + 1
        # per-step config read (sub-µs vs a ms-scale step) so
        # mx.config.set("nan_sentinel", ...) takes effect mid-run
        sentinel = _config.get("nan_sentinel")
        # mx.trace: decided up front so an unsampled step pays nothing
        # beyond one modulo (not live: the local bool alone). A cache-miss
        # step traces regardless of sampling — compiles are always-record
        # events (rare, seconds-scale). A traced step is never fenced for
        # the trace's sake: device time is the device trace's to give
        tracing = tr and (is_miss or _trace.sampled(step_no))
        # mx.goodput accounts every completed step (replay-aware) — one
        # module bool here, like the other observers
        accounting = _goodput._enabled
        observing = (_telemetry._enabled or _diagnostics._enabled or sentinel
                     or _inspect._enabled or tracing or accounting)
        # a miss is always stamped: mx.trace.setup()["compile_s"] sums the
        # builds (seconds each, never on a steady step)
        t_build = time.perf_counter() if is_miss else None
        if is_miss:
            self._step_cache[key] = self._build_step(len(data), len(labels), shapes)
        if is_miss:
            # entries from a previous remat epoch are dead for EVERY shape
            # (remat() bumped the epoch exactly so they never run again):
            # evict them or each mid-run policy change leaks one compiled
            # executable per cached shape
            for k in [k for k in self._step_cache if k[5] != key[5]]:
                del self._step_cache[k]
        if is_miss and key[3] is not None:
            # in-jit-lr executables are keyed on the schedule's values:
            # evict the stale entry so set_learning_rate / scheduler-edit
            # loops don't accumulate one dead executable per value
            for k in [k for k in self._step_cache
                      if k[:3] == key[:3] and k[4:] == key[4:]
                      and k[3] != key[3]]:
                del self._step_cache[k]
        if _resilience._enabled:
            # the `oom@step:N` injection fires here — BEFORE any transfer
            # or dispatch, like a pre-flight rejection, so the donated
            # train state is intact and every degradation-ladder rung is
            # drivable in tests
            _resilience.fault_point("dispatch", step=step_no)
        if _guard._enabled:
            # mx.guard liveness: beat the dispatch (rate-limited file
            # write) and suspend the collective deadline across a cold
            # executable build — a minutes-scale first compile is a
            # legitimate non-step region, not a dead peer
            _guard.step_begin(step_no, compiling=is_miss)
        scalars = ()
        lr_host = None
        if not self._lr_inside:
            # untraceable (custom) schedule: lr stays host-computed, one
            # scalar transfer per step — the documented fallback. Computed
            # ONCE (a custom scheduler may be stateful; the diagnostics
            # record below reuses this value rather than re-invoking it)
            lr_host = self.fopt.lr_at(step_no)
            scalars = (jnp.asarray(lr_host, jnp.float32),)
        shardings = self._batch_shardings(len(data), len(labels), shapes)
        # prefetch_to_mesh already staged these: an array whose sharding
        # matches the target skips device_put entirely (no transfer, no
        # new buffer) — that is the zero-copy hot path ci sanity asserts
        batch = [b if getattr(b, "sharding", None) == s
                 else jax.device_put(b, s)
                 for b, s in zip(batch, shardings)]
        lint_traced = None
        if is_miss and _check._enabled:
            # mx.check graph lint for the fresh step executable, BEFORE
            # its first dispatch (trace-only — no compile, no transfer;
            # the global RNG key is read without advancing the stream):
            # donation misses, baked constants, dtype promotions,
            # degenerate sharding, retrace hazards. The trace is handed
            # to memsafe's preflight below so check+memsafe together
            # cost ONE trace per miss, not two
            lint_args = (self.params, self.aux, self.opt_state,
                         self._t_dev) + scalars \
                + (_random.get_state(),) + tuple(batch)
            if _memsafe._enabled:
                lint_traced = _check.trace_jit(self._step_cache[key],
                                               lint_args)
            try:
                _check.check_step(self, key, self._step_cache[key],
                                  lint_args, batch=batch,
                                  traced=lint_traced)
            except _check.CheckError:
                # check=error: the rejected executable must not stay
                # cached — a retried same-shape call would skip the lint
                del self._step_cache[key]
                raise
        # StepTraceAnnotation: jax.profiler device traces group work by
        # train step (the reference profiler's per-iteration ranges —
        # SURVEY §5.1); free when no trace is active
        t_step = time.perf_counter() if observing else None
        in_scope = _diagnostics._enabled
        if in_scope:
            # the watchdog names this scope when the step never completes:
            # with >1 reducing device a hang here is almost always the
            # gradient psum waiting on a straggler/dead rank
            _diagnostics._scope_begin(
                "sharded_step(psum)" if self._tele_reduce_bytes
                else "sharded_step(dispatch)", step_no)
        prefl = None
        try:
            rngk = _random.next_key()
            if is_miss and _memsafe._enabled:
                # pre-flight budget check for the fresh executable, BEFORE
                # its first dispatch: AOT lower+compile (warm via the
                # persistent cache for the lazy first call below) and
                # compare execution peak + resident state/batch against
                # device capacity. A predicted overrun raises
                # MemoryBudgetError with everything intact — the
                # oom_recover=auto ladder (or the caller) re-plans
                try:
                    prefl = _memsafe.preflight_step(
                        self, key, self._step_cache[key],
                        (self.params, self.aux, self.opt_state,
                         self._t_dev) + scalars + (rngk,) + tuple(batch),
                        traced=lint_traced)
                except _memsafe.MemoryBudgetError:
                    # a rejected executable must not stay cached: a
                    # retried same-shape call would hit the cache and
                    # dispatch past the check
                    del self._step_cache[key]
                    raise
            if is_miss:
                # before the call (the train state is donated to it): the
                # jit and this call's avals, for mx.trace.scope_map()
                _trace.note_executable(
                    "train.step", self._step_cache[key],
                    (self.params, self.aux, self.opt_state, self._t_dev)
                    + tuple(scalars) + (rngk,) + tuple(batch))
            # sampled steps also carry an mx.trace annotation so the XLA
            # device trace groups this step's kernels under the same
            # (rank, step) tag as the host spans; `step.dispatch` is the
            # jitted call itself, argument handling through enqueue (a
            # miss records `step.compile` instead)
            ann = _trace.annotate(step_no) if tracing else _NULLCTX
            disp = _trace.span("step.dispatch", cat="step", step=step_no) \
                if tracing and not is_miss else _NULLCTX
            with jax.profiler.StepTraceAnnotation("train_step",
                                                  step_num=step_no), \
                    ann, disp:
                loss, self.params, self.aux, self.opt_state, self._t_dev = \
                    self._step_cache[key](
                        self.params, self.aux, self.opt_state, self._t_dev,
                        *scalars, rngk, *batch)
            t_disp = time.perf_counter() if tracing else None
            if is_miss:
                _trace.note_setup("compile_s", time.perf_counter() - t_build)
            self.num_update = step_no
            fenced = False
            if observing:
                if _telemetry._enabled or sentinel or _inspect._enabled \
                        or accounting:
                    # fence on the loss (one output of the step executable
                    # fences the whole executable) so the histogram records
                    # device step time, not just async dispatch.
                    # Diagnostics-only mode
                    # skips the fence — a ring append must not cost the
                    # host/device overlap — so its records mean "step
                    # dispatched" there. Inspect fences too: its step time
                    # is the MFU denominator and must be device time.
                    # mx.trace never fences: it would serialise the
                    # prefetch/dispatch overlap it is there to show
                    jax.block_until_ready(loss)
                    fenced = True
                t_done = time.perf_counter()
                if _telemetry._enabled:
                    self._tele_record_step(batch, t_build, t_step)
                if _diagnostics._enabled or sentinel:
                    self._diag_record_step(
                        loss,
                        lr_host if lr_host is not None
                        else self.fopt.lr_at(self.num_update),
                        shapes, t_build, sentinel)
                if tracing:
                    self._trace_record_step(step_no, t_build, t_disp,
                                            t_done, fenced)
                if accounting:
                    # before inspect (whose miss-path analysis takes
                    # real wall time): the step's interval must end at
                    # the fence, not at the analyzer
                    _goodput.note_step(step_no, t_build, t_step, t_done)
                if _inspect._enabled:
                    # LAST observer: the miss-path analysis lower+compile
                    # takes real wall time that must not leak into the
                    # compile_seconds / ring compile records above. When
                    # the memsafe preflight already analyzed this
                    # executable and handed it to inspect, skip the
                    # duplicate compile
                    self._inspect_record_step(
                        key, scalars, rngk, batch, t_build, t_step, t_done,
                        prerecorded=bool(prefl
                                         and prefl.get("inspect_recorded")))
            if not fenced and fence_every \
                    and self.num_update % int(fence_every) == 0:
                # bound async run-ahead: without an observer fencing for
                # us (diagnostics-only mode included), the host could
                # otherwise queue unbounded steps (and their batch
                # buffers) ahead of the device
                jax.block_until_ready(loss)
        finally:
            if in_scope:
                _diagnostics._scope_end()
        if _resilience._enabled:
            # periodic verified checkpoint, fault injection, and the
            # graceful-preemption final save + EXIT_PREEMPTED — all behind
            # one module-bool check on the disabled fast path
            _resilience.on_step(self)
        if _guard._enabled:
            # mx.guard: completed-step heartbeat (feeds the supervisor's
            # staleness clock AND re-arms the collective deadline), then
            # the SDC digest vote on its sdc_check_every cadence — after
            # resilience so a just-injected corrupt_grad is caught by
            # the vote this same boundary
            _guard.on_step(self, step_no)
        if _scope._enabled:
            # mx.scope live introspection: stamp the completed step for
            # /healthz + /statusz and drive an armed /profilez device
            # capture at this boundary, on this thread — the capture
            # start/stop must never race a dispatching step
            _scope.on_step(self, step_no)
        return NDArray(loss)

    def _trace_record_step(self, step_no, t_build, t_disp, t_done, fenced):
        """What mx.trace records after the call of one SAMPLED step
        (`train.step` and `step.dispatch` are live spans around it): the
        fence (t_disp→t_done), only where another observer fenced, since a
        trace never fences for itself; and the skew-probe tick at the
        collective boundary. A cache-miss step records ONE compile span
        (build through the first call's return, or through the fence
        where there was one) instead of a dispatch: it is
        compile-dominated and would poison the step category the verdict
        sums, the same exclusion the telemetry step histogram makes."""
        if t_build is not None:
            _trace.record_span("step.compile", t_build, t_done,
                               step=step_no, cat="compile", always=True,
                               block=type(self.block).__name__)
        elif fenced:
            _trace.record_span("step.fence", t_disp, t_done, step=step_no,
                               cat="step")
        _trace.skew_tick(step_no)

    def _diag_record_step(self, loss, lr, shapes, t_build, sentinel):
        """Flight-recorder entry for one sharded step; with the
        nan_sentinel knob on (works with diagnostics off too — the dump
        then just has an empty ring), the loss is host-fetched and
        checked here; NonFiniteError propagates after the post-mortem."""
        if t_build is not None:
            _diagnostics.record_event(
                "compile",
                block=f"ShardedTrainer({type(self.block).__name__})",
                compile_time_s=round(time.perf_counter() - t_build, 6),
                step=self.num_update)
        loss_val = _diagnostics._scalar(loss) if sentinel else None
        _diagnostics.record_step(
            self.num_update, loss=loss_val, lr=float(lr), shapes=shapes,
            trainer="ShardedTrainer", compiled=t_build is not None)
        if sentinel:
            # checked AFTER recording so the fatal step — non-finite loss
            # included — is the ring's last entry in the post-mortem
            _diagnostics.sentinel_check(loss_val, "loss", self.num_update)

    def _inspect_record_step(self, key, scalars, rngk, batch, t_build,
                             t_step, t_done, prerecorded=False):
        """Cost attribution for one sharded step. On a step-cache miss the
        freshly built executable is lowered+compiled once more for XLA
        cost/memory analysis (warm via the persistent compile cache;
        the post-call state has the same avals
        and shardings the executed call had, donation included). On a warm
        step the fenced dispatch→fence window [t_step, t_done] feeds the
        executable's MFU denominator — compile steps are excluded, like
        the telemetry histogram, and so is the other observers' own
        recording overhead (t_done is stamped right after the fence)."""
        name = f"ShardedTrainer({type(self.block).__name__})"
        ikey = _inspect.key_repr(key)
        if t_build is not None:
            if not prerecorded:
                _inspect.analyze_jit(
                    name, ikey, self._step_cache[key], self.params,
                    self.aux, self.opt_state, self._t_dev, *scalars, rngk,
                    *batch, collectives=self._coll_est)
        elif t_step is not None:
            _inspect.note_step(name, ikey, t_done - t_step)

    def _tele_record_step(self, batch, t_build, t_step):
        """Telemetry for one sharded step: compile accounting on a
        step-cache miss (with a signature diff explaining the re-jit),
        step latency, and gradient-reduction collective bytes. The jitted
        call compiles lazily on its first invocation, so t_build brackets
        build + first call."""
        now = time.perf_counter()
        if t_step is not None and t_build is None:
            # compile steps are excluded: the lazy first invocation would
            # put a seconds-long compile into the step histogram and poison
            # p99 / the input-stall denominator (it lands in compile_seconds)
            _M_STEP_SECONDS.observe(now - t_step)
            _telemetry.event("step", dur_s=round(now - t_step, 6),
                             step=self.num_update)
        if t_build is not None:
            dt = now - t_build
            _M_COMPILES.inc()
            _M_COMPILE_SECONDS.observe(dt)
            sig = _telemetry.signature(batch)
            causes, changed = _telemetry.diff_signature(self._tele_sig, sig)
            kind = "compile" if self._tele_sig is None else "recompile"
            if self._tele_sig is not None:
                _M_RECOMPILES.inc()
            self._tele_sig = sig
            _telemetry.event(
                kind, block=f"ShardedTrainer({type(self.block).__name__})",
                compile_time_s=round(dt, 6), causes=causes, changed=changed,
                signature=sig)
        for op, nbytes in self._tele_coll.items():
            _M_COLL_CALLS.labels(op=op).inc()
            _M_COLL_BYTES.labels(op=op).inc(nbytes)

    # ------------------------------------------------------------------
    def sync_to_block(self):
        """Write device state back into the gluon Parameters (checkpointing)."""
        params = self._fl.unflatten(self.params) if self._fused else self.params
        for (_, p), v in zip(self._grad_params, params):
            p.data()._data = v
        for (_, p), v in zip(self._aux_params, self.aux):
            p.data()._data = v

    def save_checkpoint(self, prefix):
        self.sync_to_block()
        self.block.save_parameters(prefix + ".params")

    # -- sharded checkpoint/resume (reference: Module.save_checkpoint +
    #    save_optimizer_states; here orbax writes each shard from the host
    #    that owns it, the TPU answer to dmlc::Stream .params files) ------
    def _state_pytree(self):
        """The checkpointed state, used by BOTH save and restore so the
        two can never drift apart."""
        if self._fused:
            # canonical per-tensor layout so fused-LAMB checkpoints stay
            # portable across param modes (f32: master precision preserved)
            m = self._fl.unflatten_master(self.opt_state[0])
            v = self._fl.unflatten_master(self.opt_state[1])
            return {
                "params": self._fl.unflatten_master(self.params),
                "aux": list(self.aux),
                "opt_state": [[mi, vi] for mi, vi in zip(m, v)],
                "num_update": jnp.asarray(self.num_update),
            }
        return {
            "params": list(self.params),
            "aux": list(self.aux),
            "opt_state": [list(st) for st in self.opt_state],
            "num_update": jnp.asarray(self.num_update),
        }

    def save_states(self, directory):
        """Write params + optimizer state + step count + the global RNG
        stream as an orbax sharded checkpoint (works multi-host: each
        process writes only its local shards)."""
        _ckpt_save(self, directory)

    def load_states(self, directory, reshard=None):
        """Restore a save_states() checkpoint onto the current mesh. A
        checkpoint written on a DIFFERENT topology (mesh shape or param
        mode) is redistributed bit-exactly while `reshard` allows it:
        None reads the `reshard` knob (default 'auto'), 'auto'/'host'
        redistribute, 'off' raises MeshMismatchError on any mismatch."""
        state = _ckpt_restore(self, directory, reshard)
        if self._fused:
            # a zero'd trainer re-flattens into its SHARDED resident
            # layout (checkpoints stay canonical per-tensor either way)
            pspec = self._zero_flat if self._zero else self._rep
            self.params = jax.device_put(
                self._fl.flatten(state["params"]), pspec)
            mdt = self._fl.moments_dtype
            self.opt_state = (
                jax.device_put(self._fl.flatten(
                    [st[0] for st in state["opt_state"]], mdt), pspec),
                jax.device_put(self._fl.flatten(
                    [st[1] for st in state["opt_state"]], mdt), pspec))
        else:
            self.params = list(state["params"])
            self.opt_state = [tuple(st) for st in state["opt_state"]]
        self.aux = list(state["aux"])
        self.num_update = int(state["num_update"])
        # re-seed the device-resident step counter from the restored count
        self._t_dev = jax.device_put(
            jnp.asarray(self.num_update, jnp.int32), self._rep)

    def predict_step_bytes(self, data, labels):
        """AOT memory plan for one train step at these batch SHAPES — no
        device step executes, no batch transfers: the step is built and
        lowered against ShapeDtypeStruct avals for the batch (host numpy /
        NDArray / jax arrays all work, only shape+dtype are read), compiled
        analytically, and XLA's memory_analysis is combined with the
        resident train-state bytes. Returns {"exec_peak_bytes",
        "resident_bytes", "predicted_bytes", "capacity_bytes",
        "headroom_bytes", "fits"} (exec_peak None when the backend
        withholds it; capacity/headroom/fits None when no capacity is
        known). This is what dataflow.autofit binary-searches over."""
        data = data if isinstance(data, (list, tuple)) else [data]
        labels = labels if isinstance(labels, (list, tuple)) else [labels]
        if not self._ready:
            raise RuntimeError(
                "predict_step_bytes needs materialized parameters — run "
                "one step (or use explicit shapes) before planning")

        def aval(b):
            raw = b._data if isinstance(b, NDArray) else b
            return jax.ShapeDtypeStruct(tuple(raw.shape), raw.dtype)

        batch = [aval(b) for b in list(data) + list(labels)]
        shapes = tuple(b.shape for b in batch)
        jitted = self._build_step(len(data), len(labels), shapes)
        scalars = () if self._lr_inside else (
            jax.ShapeDtypeStruct((), jnp.float32),)
        # the global key is a concrete array already on device — passing
        # it to lower() reads its aval only, and unlike next_key() it does
        # not advance the training RNG stream
        rng = _random.get_state()
        args = (self.params, self.aux, self.opt_state, self._t_dev) \
            + scalars + (rng,) + tuple(batch)
        exec_peak, _compiled, err = _memsafe._analyze(jitted, args)
        resident = _memsafe.resident_bytes(
            (self.params, self.aux, self.opt_state)) \
            + sum(int(math.prod(s.shape)) * s.dtype.itemsize for s in batch)
        capacity = _memsafe.capacity_bytes()
        predicted = int(resident) + int(exec_peak or 0)
        out = {
            "exec_peak_bytes": exec_peak,
            "resident_bytes": int(resident),
            "predicted_bytes": predicted,
            "capacity_bytes": capacity,
            "headroom_bytes": None if capacity is None
            else int(capacity) - predicted,
            "fits": None if capacity is None else predicted <= capacity,
        }
        if err is not None:
            out["analysis_error"] = err
        return out

    @property
    def param_count(self):
        if self._fused:
            return sum(self._fl.sizes)
        return sum(int(jnp.size(p)) for p in self.params)


# -- shared checkpoint plumbing (ShardedTrainer + pipeline trainers) -------


def _orbax_write(trainer, directory):
    """Orbax save of the trainer's _state_pytree PLUS the global RNG
    stream, so a resumed run replays the same dropout/shuffle draws
    (trajectory-exact resume)."""
    import os

    import orbax.checkpoint as ocp

    from .. import random as _random

    state = trainer._state_pytree()
    state["rng_key"] = jax.random.key_data(_random.get_state())
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(os.path.join(str(directory), "state")),
               state, force=True)
    ckptr.wait_until_finished()


def _ckpt_save(trainer, directory):
    """Write one trainer checkpoint. With mx.resilience enabled the write
    is atomic and verified: state lands in a temp directory, a
    manifest.json with per-file checksums + step + mesh fingerprint is
    fsynced next to it, and the whole directory renames into place — a
    kill mid-save can never leave a checkpoint that restore would trust.
    Disabled (the default) keeps the plain orbax write: no temp copy, no
    hashing, byte-for-byte the old behavior."""
    if not _resilience._enabled:
        _orbax_write(trainer, directory)
        return
    from . import reshard as _reshard
    _resilience.write_checkpoint(
        directory, lambda tmp: _orbax_write(trainer, tmp),
        step=int(trainer.num_update),
        fingerprint=_resilience.trainer_fingerprint(trainer),
        layouts=_reshard.state_layouts(trainer))


def _ckpt_restore(trainer, directory, reshard=None):
    """Restore + re-seed the global RNG. Returns the state pytree for the
    trainer to apply its fields from. With mx.resilience enabled and a
    manifest present, checksums are verified first (raising
    CheckpointCorruptError on a torn/corrupt checkpoint) and the mesh/
    param-mode fingerprint is compared: a topology change is REDISTRIBUTED
    onto the current mesh while the `reshard` policy allows it (the knob,
    or the explicit load_states(reshard=...) argument) — planned from the
    manifest's recorded per-array shardings, executed by orbax reading
    each target shard's byte range from disk (peak memory bounded per
    array, no device all-gather), recorded in reshard telemetry and the
    post-mortem resume section. With reshard='off' the mismatch raises
    MeshMismatchError naming both fingerprints."""
    import os

    import orbax.checkpoint as ocp

    from .. import random as _random

    plan = None
    manifest = None
    t0 = time.perf_counter()
    if _resilience._enabled and os.path.exists(
            os.path.join(str(directory), "manifest.json")):
        manifest = _resilience.verify_checkpoint(directory)
        if _resilience.reshard_gate(manifest, trainer, str(directory),
                                    reshard):
            from . import reshard as _reshard
            plan = _reshard.plan_restore(manifest, trainer)
    target = trainer._state_pytree()
    target["rng_key"] = jax.random.key_data(_random.get_state())
    ckptr = ocp.StandardCheckpointer()
    state = ckptr.restore(
        os.path.abspath(os.path.join(str(directory), "state")), target)
    if plan is not None:
        from . import reshard as _reshard
        _reshard.note_reshard(
            "restore", plan, time.perf_counter() - t0,
            src_fp=manifest.get("fingerprint"),
            dst_fp=_resilience.trainer_fingerprint(trainer))
    _random.set_state(state["rng_key"])
    return state


class PipelineCheckpointMixin:
    """save_states/load_states for the pipeline trainers: their state is a
    flat param list + per-param opt-state tuples + the step count (no aux
    — BatchNorm stats inside pipeline stages raise at construction)."""

    def _state_pytree(self):
        return {
            "params": list(self.params),
            "opt_state": [list(st) for st in self.opt_state],
            "num_update": jnp.asarray(self.num_update),
        }

    def _ensure_setup(self):
        # the hetero PipelineTrainer defers _setup() to its first step (to
        # resolve deferred param shapes from a probe batch); restoring into
        # a FRESH trainer must materialize params first. Works only when
        # every stage block has explicit shapes — deferred-shape stages
        # need one step before load_states.
        if not getattr(self, "_ready", True) and not hasattr(self, "params"):
            self._setup()
            self._ready = True

    def save_states(self, directory):
        _ckpt_save(self, directory)

    def load_states(self, directory, reshard=None):
        self._ensure_setup()
        state = _ckpt_restore(self, directory, reshard)
        self.params = list(state["params"])
        self.opt_state = [tuple(st) for st in state["opt_state"]]
        self.num_update = int(state["num_update"])
