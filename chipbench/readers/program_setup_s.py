"""One row, in seconds, of the program's always-on set-up table
(`mxnet_tpu.trace.setup()`: `import_s`, `initialize_s`, `compile_s`)."""


def read(result, key):
    from mxnet_tpu import trace
    table = getattr(trace, "setup", None)
    return table().get(key) if table else None
