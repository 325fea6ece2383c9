"""The whole step's share of the chip's peak, in percent: the operations
the model needs for one step on one chip (`work_fn`, as `<module under
chipbench>.<function>`, from `result["shapes"]`) over the traced stretch's
length on the host clock per step, idle time and all, times the chip's peak
bf16 rate (peaks.json). A kernel's roofline share goes silent when a later
change takes the kernel off the path; this does not."""
import importlib


def read(result, work_fn):
    steps, seconds = result.get("traced_steps"), result.get("traced_window_s")
    if not steps or not seconds or not result.get("peaks"):
        return None
    module, _, fn = work_fn.rpartition(".")
    try:
        flops, _ = getattr(importlib.import_module(
            "chipbench." + module), fn)(result["shapes"])
    except KeyError:        # the kind's shapes lack what the function reads
        return None
    return 100.0 * flops / (seconds / steps) \
        / result["peaks"]["bf16_flops_per_s"]
