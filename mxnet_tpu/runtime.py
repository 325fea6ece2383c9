"""Runtime feature detection (reference: `python/mxnet/runtime.py` —
`Features` / `feature_list()`, the `libinfo` surface that reports which
capabilities this build has, e.g. CUDA/CUDNN/MKLDNN there; TPU/PALLAS/
native-IO here)."""
from __future__ import annotations

import os

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    __slots__ = ("name", "enabled")

    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return f"{'✔' if self.enabled else '✖'} {self.name}"


def _detect():
    feats = {}

    def add(name, fn):
        try:
            feats[name] = bool(fn())
        except Exception:
            feats[name] = False

    import jax

    add("TPU", lambda: any(d.platform == "tpu" for d in jax.devices()))
    add("BF16", lambda: True)              # XLA bf16 everywhere
    add("PALLAS", lambda: __import__(
        "mxnet_tpu.pallas_ops._common",
        fromlist=["pallas_available"]).pallas_available())
    add("DIST_KVSTORE", lambda: True)      # mesh/collective backend
    # io.native owns the .so path AND builds it on first use — ask it
    add("NATIVE_IO", lambda: __import__(
        "mxnet_tpu.io.native", fromlist=["available"]).available())
    add("ONNX", lambda: True)              # in-tree wire codec
    add("INT8_QUANTIZATION", lambda: True)
    add("PROFILER", lambda: True)
    add("CUDA", lambda: False)             # by design: no CUDA in build
    add("CUDNN", lambda: False)
    add("MKLDNN", lambda: False)
    return feats


class Features(dict):
    """Mapping name -> Feature; `Features().is_enabled('TPU')` matches the
    reference API."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name):
        key = name.upper()
        if key not in self:
            raise RuntimeError(f"unknown feature '{name}'; known: "
                               f"{sorted(self)}")
        return self[key].enabled

    def __repr__(self):
        return "[" + ", ".join(repr(v) for v in self.values()) + "]"


def feature_list():
    return list(Features().values())
