"""Share of a roofline, in percent, of the operations the program traced
under the `jax.named_scope`s in `scopes`: the least time the chip needs
for the stretch's work at its published peaks (`work_fn`, as
`<module under chipbench>.<function>`, from `result["shapes"]`;
`work.least_seconds`) over the device self time under the scopes
(`trace_scope_ms_per_step`, its clock join and its arguments). Whatever
implements the scopes, XLA operations or a kernel, the share reads the
same work. None where the program has no such scope or the run no trace."""
import importlib

from chipbench import work
from chipbench.readers import trace_scope_ms_per_step


def read(result, work_fn, **scope_args):
    ms = trace_scope_ms_per_step.read(result, **scope_args)
    if not ms or "traced" not in result.get("shapes", {}):
        return None
    module, _, fn = work_fn.rpartition(".")
    flops, nbytes = getattr(importlib.import_module(
        "chipbench." + module), fn)(result["shapes"])
    least, _ = work.least_seconds(flops, nbytes, result["peaks"])
    return 100.0 * least / (ms / 1e3)
