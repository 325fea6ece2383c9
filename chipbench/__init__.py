"""chipbench — the on-chip benchmark of mxnet_tpu (see README.md).

A regular package on purpose: `tests/chipbench/` has the same name, and
without this file the two would merge into one namespace package.
"""
