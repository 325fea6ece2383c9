"""The measuring protocol every kind's driver shares.

Warm-up steps, garbage collection frozen and off, the window (the clock
stops after `settle()`, the driver's wait for the device), then, in the
traced run only, a stretch of `trace_steps` more steps under the profiler.
All three phases run the driver's one `one_step`, so the step is traced
once, from one Python stack.

What closes the window is the kind's choice. Without `count` the clock
does, at the first step boundary after `--seconds` (training: every step
is the same work). With `count` (`ByCount`, the serving kinds) the clock
only measures: the warm-up runs its steps and then on until `ready()`, the
window closes at the first step boundary at which `work()` has grown by
`target` since it opened, and in a traced run it closes where the stretch
has to begin, at step `trace_from` of the sequence (the end-to-end numbers
of a traced run are not used). So every run of a cell measures the same
steps, whatever the machine's pace. A counted window that lasts over twice
`--seconds` is closed there and marked `overran`: the kind reports the run
as failed rather than hang.
"""
import collections
import gc
import time

from chipbench import xplane

Window = collections.namedtuple(
    "Window", "steps t0 t1 setup_s recording traced_steps warmup_steps "
    "overran")

# work(): a count of work done so far that only grows (tokens emitted);
# target: how much of it a window holds; ready(): whether the warm-up may
# end; trace_from: the step, counted from the first warm-up step, at which
# the traced stretch begins.
ByCount = collections.namedtuple("ByCount", "work target ready trace_from")


def measure(ctx, one_step, settle, warmup_steps, trace_steps, count=None):
    warmed = 0
    while warmed < warmup_steps or (count and not count.ready()):
        one_step()
        settle()
        warmed += 1
    gc.collect()
    gc.freeze()
    gc.disable()
    overran = False
    try:
        t0 = time.perf_counter()
        steps = 0
        opened_at = count.work() if count else None
        while True:
            one_step()
            steps += 1
            if ctx.steps is not None:       # the tests' window of N steps
                if steps >= ctx.steps:
                    break
            elif count is None:
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            elif ctx.trace:
                if warmed + steps >= count.trace_from:
                    break
            elif count.work() - opened_at >= count.target:
                break
            elif time.perf_counter() - t0 > 2 * ctx.seconds:
                overran = True
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
                  trace_steps if recording else 0, warmed, overran)


def trace_result(window):
    """The keys of a driver's result that the trace readers take."""
    rec = window.recording
    return {"trace": rec.trace if rec else None,
            "traced_steps": window.traced_steps,
            "traced_window_s": rec.seconds if rec else None}
