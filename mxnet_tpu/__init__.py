"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capabilities.

Brand-new design (not a port): jax/XLA is the execution engine, Pallas the
kernel language, GSPMD mesh sharding the distribution layer. The public
surface mirrors the reference framework (`python/mxnet/`) so reference users
find everything where they expect it: `nd`, `autograd`, `gluon`, `optimizer`,
`metric`, `io`, `kvstore`, `module`, `profiler`.
"""
from __future__ import annotations

import time as _time

_t_import = _time.perf_counter()    # mx.trace.setup()["import_s"]

__version__ = "0.1.0"

from . import base
from .base import MXNetError
from . import context
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import random
from . import config
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import attribute
from .attribute import AttrScope
from .debug import debug

__all__ = [
    "nd", "ndarray", "autograd", "random", "context", "attribute",
    "AttrScope", "Context", "cpu", "gpu", "tpu", "current_context",
    "num_gpus", "num_tpus", "MXNetError", "config", "debug",
]

# Subpackages filled in over the build; imported lazily to keep import light
# and to avoid hard failures while the surface is under construction.
_LAZY = {
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "init": ".initializer",
    "initializer": ".initializer",
    "metric": ".metric",
    "callback": ".callback",
    "io": ".io",
    "kv": ".kvstore",
    "kvstore": ".kvstore",
    "mod": ".module",
    "module": ".module",
    "sym": ".symbol",
    "symbol": ".symbol",
    "model": ".module",
    "mon": ".monitor",
    "monitor": ".monitor",
    "name": ".name",
    "runtime": ".runtime",
    "operator": ".operator",
    "profiler": ".profiler",
    "telemetry": ".telemetry",
    "diagnostics": ".diagnostics",
    "resilience": ".resilience",
    "memsafe": ".memsafe",
    "check": ".check",
    "guard": ".guard",
    "goodput": ".goodput",
    "scope": ".scope",
    "serve": ".serve",
    "pages": ".pages",
    "trace": ".trace",
    "inspect": ".inspect",
    "dataflow": ".dataflow",
    "parallel": ".parallel",
    "test_utils": ".test_utils",
    "lr_scheduler": ".lr_scheduler",
    "image": ".image",
    "contrib": ".contrib",
    "recordio": ".io.recordio",
    "rtc": ".rtc",
    "visualization": ".visualization",
    "viz": ".visualization",
    "engine": ".engine",
    "executor": ".symbol.executor",
    "registry": ".registry",
    "util": ".util",
}


def __getattr__(name):
    import importlib
    if name in _LAZY:
        mod = importlib.import_module(_LAZY[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute '{name}'")


_import_s = _time.perf_counter() - _t_import
