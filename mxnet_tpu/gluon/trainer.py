"""Trainer: applies an Optimizer to a set of Parameters.

Reference: `python/mxnet/gluon/trainer.py` — there, `step()` pushes/pulls
every gradient through a KVStore (per-tensor allreduce) then runs the update
op per parameter. TPU-native: gradients living on a sharded mesh are already
reduced by XLA collectives inside the jitted backward (psum on the data
axis), so `step()` is just the update kernels; the kvstore argument is
accepted for API compatibility and validated against the mesh story
(`mxnet_tpu.kvstore`).
"""
from __future__ import annotations

import time

from .. import config as _config
from .. import diagnostics as _diagnostics
from .. import memsafe as _memsafe
from .. import optimizer as opt_mod
from .. import telemetry as _telemetry
from ..ndarray import NDArray
from .parameter import ParameterDict

__all__ = ["Trainer"]

_M_STEP_SECONDS = _telemetry.histogram(
    "trainer_step_seconds", "Trainer.step / ShardedTrainer.step host wall "
    "time (optimizer apply; the sharded path fences on the step's outputs, "
    "so this is device step time)")


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = [params[k] for k in sorted(params.keys())] \
                if isinstance(params, dict) else list(params.values())
        if compression_params is not None:
            raise ValueError(
                "Trainer does not route gradients through a kvstore on TPU "
                "(XLA collectives do the reduction inside the jitted "
                "step), so compression_params has nothing to compress "
                "here. Use the explicit kvstore path instead: "
                "kv = mx.kv.create(...); kv.set_gradient_compression(...)")
        self._params = [p for p in params if p.grad_req != "null"]
        self._all_params = list(params)
        optimizer_params = optimizer_params or {}
        self._optimizer = opt_mod.create(optimizer, param_dict={
            i: p for i, p in enumerate(self._params)}, **optimizer_params)
        self._states = [None] * len(self._params)
        self._states_created = False
        self._kvstore_type = kvstore
        self._num_update = 0
        # arm mx.memsafe iff its knobs ask — construction-time reads only
        _memsafe.maybe_enable()

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _create_states(self):
        for i, p in enumerate(self._params):
            self._states[i] = self._optimizer.create_state(i, p.data())
        self._states_created = True

    def step(self, batch_size, ignore_stale_grad=False):
        """Scale gradients by 1/batch_size and apply updates. When AMP is
        attached (contrib.amp.init_trainer), also unscale by the dynamic
        loss scale and skip non-finite steps."""
        if _telemetry._enabled:
            t0 = time.perf_counter()
            try:
                self._step_guarded(batch_size, ignore_stale_grad)
            finally:
                _M_STEP_SECONDS.observe(time.perf_counter() - t0)
            return
        self._step_guarded(batch_size, ignore_stale_grad)

    def _step_guarded(self, batch_size, ignore_stale_grad):
        try:
            self._step_impl(batch_size, ignore_stale_grad)
        except Exception as e:  # noqa: BLE001 — classified below
            # mx.memsafe: the eager path cannot degrade a step whose tape
            # already ran, but an OOM here still counts oom_events_total
            # and the error gains the remediation story. Disabled
            # (default): one module-bool read on an already-failing path
            if _memsafe._enabled and _memsafe.is_oom(e):
                _memsafe.note_eager_oom(e, step=self._num_update)
            raise

    def _step_impl(self, batch_size, ignore_stale_grad):
        self._num_update += 1
        scaler = getattr(self, "_amp_loss_scaler", None)
        amp_scaled = scaler is not None and scaler.loss_scale != 1.0
        # per-step config read (dict + uncontended lock, sub-µs vs a
        # ms-scale step) so mx.config.set takes effect mid-run; the
        # per-record fast path inside diagnostics stays a single bool
        sentinel = _config.get("nan_sentinel")
        if _diagnostics._enabled or sentinel:
            # flight-recorder entry BEFORE the update so the sentinel can
            # stop a non-finite gradient from reaching the parameters.
            # With a scaling AMP trainer attached the sentinel stands
            # down: Inf grads there are a routine scale-too-high overflow
            # that the scaler below handles by skipping the step, not a
            # run-killing event
            gnorm = None
            if sentinel and not amp_scaled:
                gnorm = _diagnostics.grad_global_norm(self._params)
            _diagnostics.record_step(
                self._num_update, lr=self.learning_rate, grad_norm=gnorm,
                trainer="Trainer")
            if gnorm is not None:
                # checked AFTER recording so the fatal step is the ring's
                # last entry (the post-mortem must show the NaN, not end
                # one step before it), but BEFORE the update applies
                _diagnostics.sentinel_check(gnorm, "grad_norm",
                                            self._num_update)
        if amp_scaled:
            # bf16's default scale of 1.0 skips the whole dance — no
            # overflow sync on the hot path (the point of bf16-first AMP)
            if getattr(scaler, "_pending_unscaled", False):
                self._optimizer.rescale_grad = 1.0 / batch_size
                scaler._pending_unscaled = False
            else:
                self._optimizer.rescale_grad = \
                    1.0 / (batch_size * scaler.loss_scale)
            overflow = scaler.has_overflow(self._params)
            scaler.update_scale(overflow)
            if overflow:
                return  # skip the update, as the reference AMP trainer does
        else:
            self._optimizer.rescale_grad = 1.0 / batch_size
        self._update(ignore_stale_grad)
        fence_every = _config.get("trainer_async_fence_every")
        if fence_every and self._num_update % int(fence_every) == 0:
            # eager update ops dispatch async too: a periodic fence bounds
            # how many in-flight updates (and their buffers) the host can
            # queue ahead of the device
            import jax
            jax.block_until_ready([p.data()._data for p in self._params])

    def update(self, batch_size, ignore_stale_grad=False):
        self.step(batch_size, ignore_stale_grad)

    def allreduce_grads(self):
        """No-op: on a sharded mesh XLA's psum already reduced gradients
        (reference: kvstore push/pull per parameter)."""

    def _update(self, ignore_stale_grad=False):
        if not self._states_created:
            self._create_states()
        for i, p in enumerate(self._params):
            self._optimizer.update(i, p.data(), p.grad(), self._states[i])

    def zero_grad(self):
        for p in self._params:
            p.zero_grad()

    # -- optimizer state checkpointing (reference: trainer.save_states) --
    def save_states(self, fname):
        from ..ndarray import ndarray as _nd
        flat = {}
        if not self._states_created:
            self._create_states()
        for i, s in enumerate(self._states):
            if s is None:
                continue
            if isinstance(s, tuple):
                for j, t in enumerate(s):
                    if t is not None:
                        flat[f"{i}.{j}"] = t
            else:
                flat[f"{i}"] = s
        _nd.save(fname, flat)

    def load_states(self, fname):
        from ..ndarray import ndarray as _nd
        if not self._states_created:
            self._create_states()
        flat = _nd.load(fname)
        for key, arr in flat.items():
            if "." in key:
                i, j = map(int, key.split("."))
                self._states[i][j]._data = arr._data
            else:
                self._states[int(key)]._data = arr._data
