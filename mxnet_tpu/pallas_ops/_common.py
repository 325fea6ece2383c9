"""Shared plumbing for the mx.kernels Pallas library.

Every kernel in this package sits behind the `kernels` knob with a
bit-exact XLA-native fallback:

  * `off`  — the fallback runs unconditionally; nothing in this module
    touches `jax.experimental.pallas` (the trainer hot loop stays free
    of the pallas import, asserted by ci/run.sh sanity).
  * `auto` (default) — the Pallas kernel engages when it can win: a TPU
    backend (or the Pallas interpreter under
    MXNET_TPU_PALLAS_INTERPRET=1, which is how tier-1 exercises the
    kernel CODE on CPU) and, for the elementwise fused-update kernels,
    at least `kernels_min_elements` elements.
  * `on`   — insist: `require()` raises when Pallas is unavailable
    instead of silently falling back (shape-eligibility still applies —
    `on` cannot make a non-divisible layout divisible).

The eligibility decision is made at TRACE time (plain Python, outside
the compiled computation), so `off` runs are byte-identical to a build
without this package: the fallback expression IS the pre-kernel code.

SPMD caveat, shared by every kernel here: `pl.pallas_call` has no GSPMD
partitioning rule — on a TPU, jit refuses a Mosaic kernel whose operands
are sharded ("Mosaic kernels cannot be automatically partitioned").
Kernels therefore run as per-device manual code: `flash_attention`
wraps itself in `shard_map` when the installed mesh spans more than one
device, `parallel/moe.py` and the ring/pipeline paths already run inside
one (`in_shard_map()`), and the global-view fused-update and
paged-attention kernels engage only when the step sees one device
(`multi_device()` is False). The per-shard MATH composes with mx.zero
regardless — `tests/unittest/test_kernels.py` pins that a sharded
application (kernel per flat shard) is bit-exact against the
whole-vector kernel.
"""
from __future__ import annotations

import os

from .. import config as _config

__all__ = ["interpret", "pallas_available", "use_pallas", "require",
           "installed_mesh", "multi_device", "in_shard_map", "min_elements",
           "load_pallas", "compiler_params", "round_up", "row8"]

# the pallas module, bound by load_pallas() at first kernel engagement —
# ONE copy of the lazy-import logic for the whole library (kernels=off /
# CPU processes never call it, so pallas stays out of sys.modules)
pl = None


def load_pallas():
    global pl
    if pl is None:
        from jax.experimental import pallas as pl_mod
        pl = pl_mod
    return pl


def compiler_params(**kw):
    """Mosaic compiler params — the one spelling every kernel module
    uses (a jax rename is a one-line fix)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kw)


def smem():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.SMEM


def round_up(x, m):
    return (x + m - 1) // m * m


def row8(x):
    """(N,) -> (8, N): the 8-sublane carrier layout for row vectors
    (the flash_attention LSE/bias convention — Mosaic wants the last two
    block dims (8k, 128k) or spanning the array)."""
    import jax.numpy as jnp
    return jnp.broadcast_to(x[None, :], (8, x.shape[0]))


def interpret():
    """MXNET_TPU_PALLAS_INTERPRET=1 routes every kernel through the
    Pallas interpreter on any backend — the only way the kernel CODE
    (not the jnp fallback) is exercised off-TPU (tier-1 + ci sanity)."""
    return os.environ.get("MXNET_TPU_PALLAS_INTERPRET", "0") == "1"


def pallas_available():
    """True when a TPU backend (or the interpreter) can run a kernel.
    Never imports `jax.experimental.pallas`: a kernels=auto process on
    CPU — and any kernels=off process — keeps pallas out of sys.modules
    entirely (ci/run.sh sanity asserts it after a trainer step +
    QuantizedDense forward). The import happens at engagement
    (`load_pallas`), and a failure there raises: on a TPU a missing
    kernel library is an error, not a reason to run the L×L fallback."""
    if interpret():
        return True
    import jax
    return jax.default_backend() == "tpu"


def use_pallas():
    """The per-call-site gate: False under kernels=off (no pallas
    import, no backend probe), else whether a kernel can actually run
    here. `on` behaves like `auto` for the decision itself — it differs
    only in that `require()` raises instead of falling back."""
    knob = _config.get("kernels")
    if knob == "off":
        return False
    ok = pallas_available()
    if not ok and knob == "on":
        require()
    return ok


def require():
    """kernels='on' insists: raise naming the reason Pallas cannot run
    instead of a silent fallback (auto's behavior)."""
    if not pallas_available():
        import jax
        raise RuntimeError(
            "kernels='on' but the Pallas path cannot run here: backend "
            f"is {jax.default_backend()!r} (need TPU, or "
            "MXNET_TPU_PALLAS_INTERPRET=1 for the interpreter). Use "
            "kernels='auto' to fall back to the XLA lowering silently.")


def installed_mesh():
    """The mesh `parallel.make_mesh`/`set_mesh` installed, or None —
    read without creating one (`parallel.current_mesh()` would)."""
    from ..parallel import mesh as _mesh
    return _mesh._current["mesh"]


def multi_device():
    """True when the step being traced spans more than one device — the
    SPMD regime where jit refuses a global-view pallas_call (see module
    docstring). The installed parallel mesh is the authority when one
    exists (a 1-device mesh on a 4-chip host is still a single-device
    step); otherwise the local device count decides. Checked at trace
    time."""
    m = installed_mesh()
    if m is not None:
        return int(m.size) > 1
    import jax
    return jax.local_device_count() > 1


def in_shard_map():
    """True while tracing the body of a `shard_map` (ring/ulysses
    attention, MoE, the pipeline trainers): arrays are already
    per-device there, so a kernel runs as it stands."""
    import jax
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def min_elements():
    return int(_config.get("kernels_min_elements"))
