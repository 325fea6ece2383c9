"""Device contexts.

TPU-native equivalent of the reference's `Context` (upstream mxnet
`include/mxnet/base.h` Context, `python/mxnet/context.py`): a lightweight
handle naming a device. `mx.gpu(i)` is kept as a compatibility alias for the
accelerator (TPU) so reference scripts run unchanged; there is no CUDA
anywhere in this build.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]

_DEVTYPE_ALIASES = {
    "gpu": "tpu",  # reference scripts say mx.gpu(); our accelerator is the TPU
    "cuda": "tpu",
}


class Context:
    """A device context. Use as a `with` block to set the default device.

    Reference: `python/mxnet/context.py` (Context.__enter__ stack semantics).
    """

    _stack = threading.local()

    def __init__(self, device_type, device_id=0):
        device_type = _DEVTYPE_ALIASES.get(device_type, device_type)
        self.device_type = device_type
        self.device_id = device_id

    # -- jax interop ------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax device this context names — or, when that
        platform is absent (a CPU-only test run), the default backend's
        device with the same index, so code written for `tpu()` still
        runs. `mx.tpu(0)` is therefore NOT proof of a TPU: a script that
        must run on the chip asserts `jax.devices()[0].platform` itself
        (chip_smoke.py does)."""
        platform = self.device_type
        try:
            devs = jax.devices(platform)
        except RuntimeError:
            devs = jax.devices()
        return devs[self.device_id % len(devs)]

    # -- scope handling ---------------------------------------------------
    def __enter__(self):
        stack = getattr(Context._stack, "contexts", None)
        if stack is None:
            stack = Context._stack.contexts = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._stack.contexts.pop()
        return False

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"


def current_context():
    stack = getattr(Context._stack, "contexts", None)
    if stack:
        return stack[-1]
    return Context(jax.default_backend(), 0)


def cpu(device_id=0):
    return Context("cpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compatibility alias: the reference's accelerator context. Maps to TPU."""
    return Context("gpu", device_id)


def _accel_count():
    try:
        return len(jax.devices("tpu"))
    except RuntimeError:
        return 0


def num_gpus():
    return _accel_count()


def num_tpus():
    return _accel_count()
