"""The one import site of `shard_map` for the package.

Every shard_map call site imports through here (tools/lint_rules.py
enforces it), so the day jax moves or renames it again is a one-line
fix. Supported jax: see README "Supported versions".
"""
from jax import shard_map

__all__ = ["shard_map"]
