"""The measuring protocol every kind's driver shares.

A count of warm-up steps (so the window opens at the same point of the work
in every run), garbage collection frozen and off, the window (closed at the
first step boundary after `--seconds`; the clock stops after `settle()`, the
driver's wait for the device), then, in the traced run only, a stretch of
`trace_steps` more steps under the profiler. All three phases run the
driver's one `one_step`, so the step is traced once, from one Python stack.
"""
import collections
import gc
import time

from chipbench import xplane

Window = collections.namedtuple(
    "Window", "steps t0 t1 setup_s recording traced_steps")


def measure(ctx, one_step, settle, warmup_steps, trace_steps):
    for _ in range(warmup_steps):
        one_step()
        settle()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        t0 = time.perf_counter()
        steps = 0
        while True:
            one_step()
            steps += 1
            if ctx.steps is not None:       # the tests' count-based window
                if steps >= ctx.steps:
                    break
            elif time.perf_counter() - t0 >= ctx.seconds:
                break
        settle()
        t1 = time.perf_counter()
        recording = None
        if ctx.trace:
            with xplane.Recording(ctx.keep_trace) as recording:
                for _ in range(trace_steps):
                    one_step()
                settle()
    finally:
        gc.enable()
        gc.unfreeze()
    return Window(steps, t0, t1, t0 - ctx.t_start, recording,
                  trace_steps if recording else 0)


def trace_result(window):
    """The keys of a driver's result that the trace readers take."""
    rec = window.recording
    return {"trace": rec.trace if rec else None,
            "traced_steps": window.traced_steps,
            "traced_window_s": rec.seconds if rec else None}
