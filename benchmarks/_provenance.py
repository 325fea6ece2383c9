"""Shared bench prologue, device provenance + mx.ledger glue.

Every bench entry point runs in ONE process on the device JAX gives it
and starts with `start()`: persistent compile cache on, device printed,
and a hard stop without a TPU — a measuring script has no CPU fallback,
because a number it prints goes under a device metric's name. Rows carry
the device they were measured on (`device_fields()`); when `ledger_dir`
is armed each bench appends one provenance-keyed run record to the
cross-run ledger (off: one bool check, zero record_run calls — asserted
by ci/run.sh).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def device_fields():
    """Where a row was measured, as JAX reports it. `smoke_mode` stays in
    the contract (always False: these scripts only run on a chip) because
    mx.ledger and tools/bench_diff.py key their series on it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "devices": len(jax.devices()), "smoke_mode": False}


def start():
    """The prologue: turn the persistent compile cache on, print the
    device to stderr, and exit non-zero unless it is a TPU whose peak is
    in mx.inspect's table. Returns `device_fields()`."""
    import jax

    from mxnet_tpu import dataflow
    from mxnet_tpu import inspect as mxinspect

    cache = dataflow.ensure_compile_cache()
    fields = device_fields()
    print(f"# jax {jax.__version__} device: {fields['platform']} "
          f"{fields['device_kind']!r} x{fields['devices']}; "
          f"compile cache: {cache}", file=sys.stderr)
    if fields["platform"] != "tpu":
        raise SystemExit(
            f"{os.path.basename(sys.argv[0])}: needs a TPU, jax found "
            f"platform {fields['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). There is no CPU "
            "fallback: run it through the chip tool.")
    if mxinspect.peak_flops_per_chip() is None:
        raise SystemExit(
            f"device_kind {fields['device_kind']!r} is not in mx.inspect's "
            "peak table (mxnet_tpu/inspect.py); add it with its source "
            "before benchmarking on it")
    return fields


def ledger_append(bench, rows, **extra):
    """The bench-side mx.ledger hook: one run record per invocation.
    With the ledger off (`ledger_dir` unset) this is one module-bool
    check and ZERO record_run calls — the ci-asserted fast path."""
    from mxnet_tpu import ledger
    if not ledger.enabled():
        return None
    return ledger.record_run(bench, rows, **extra)
