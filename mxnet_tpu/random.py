"""Random state management.

The reference keeps per-device RNG resources handed to ops by the
ResourceManager (`src/resource.cc`, `include/mxnet/resource.h`); frontend
seeding is `mx.random.seed` (`python/mxnet/random.py`). Here the equivalent is
a process-global jax PRNG key that ops split from.

Traced code (hybridized blocks, jitted train steps) must NOT capture a
concrete key — that would bake one dropout mask into the compiled program. A
`key_scope(key)` context makes `next_key()` derive deterministically from a
*traced* key via `fold_in` of a call counter, so compiled programs get fresh
randomness through an ordinary argument.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["seed", "next_key", "key_scope", "get_state"]

_local = threading.local()


def _impl():
    """PRNG implementation: threefry is counter-exact but slow on TPU's
    vector unit; the hardware `rbg` generator is ~25ms/step cheaper on a
    BERT-base train step (dropout masks dominate). Default: rbg on TPU,
    threefry elsewhere; knob: config 'prng' / MXNET_TPU_PRNG."""
    from . import config
    choice = config.get("prng")
    if choice != "auto":
        return choice
    return "rbg" if jax.default_backend() == "tpu" else "threefry2x32"


_global = {"key": None, "lock": threading.Lock()}


def _global_key():
    if _global["key"] is None:
        _global["key"] = jax.random.key(0, impl=_impl())
    return _global["key"]


def seed(seed_state):
    """Seed the global RNG (reference: `mx.random.seed`)."""
    _global["key"] = jax.random.key(int(seed_state), impl=_impl())


def get_state():
    return _global_key()


def set_state(key):
    """Restore a key captured by get_state() (accepts a typed key or the
    raw key_data a checkpoint stores)."""
    import jax

    if not jax.dtypes.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        import jax.numpy as jnp
        key = jax.random.wrap_key_data(jnp.asarray(key), impl=_impl())
    _global["key"] = key


class key_scope:
    """Within this scope, `next_key()` folds a counter into `key` instead of
    consuming global state — safe under jax tracing."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        stack = getattr(_local, "scopes", None)
        if stack is None:
            stack = _local.scopes = []
        stack.append([self.key, 0])
        return self

    def __exit__(self, *exc):
        _local.scopes.pop()
        return False


def next_key():
    stack = getattr(_local, "scopes", None)
    if stack:
        entry = stack[-1]
        entry[1] += 1
        return jax.random.fold_in(entry[0], entry[1])
    with _global["lock"]:
        _global["key"], sub = jax.random.split(_global_key())
        return sub
