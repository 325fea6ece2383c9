"""A kernel's share of its roofline, in percent: the least time the chip
needs for the step's work at its published peaks over the time the kernel's
operations took in the trace, per step and chip. `work_fn` names the
function that computes operations and bytes from the cell's shapes, as
`<module under chipbench>.<function>` (`work.flash_attention_train`);
peaks.json has the peaks."""
import importlib

from chipbench import work, xplane


def read(result, match, work_fn):
    if not result.get("trace") or not result.get("traced_steps"):
        return None
    seconds = xplane.seconds_matching(result["trace"], match)
    if seconds <= 0:
        return None
    module, _, fn = work_fn.rpartition(".")
    flops, nbytes = getattr(importlib.import_module(
        "chipbench." + module), fn)(result["shapes"])
    least, _ = work.least_seconds(flops, nbytes, result["peaks"])
    return 100.0 * least / (seconds / result["traced_steps"])
