"""Time, in ms, inside the program's own spans named `span`
(`mxnet_tpu.trace`, armed by the traced stretch's profiler session): the
sum over the spans that start inside the stretch per traced step, or with
`stat: "p50"` their median. `where` keeps the spans whose attrs meet it
(`{"chunk": ["gt", 1]}`). `outer` names the span that lies one to one in
the harness's `bench.step` (the clock join)."""
import statistics

from chipbench import program_spans


def read(result, span, outer, stat="per_step", where=None):
    found = program_spans.in_stretch(result, outer)
    if not found or not result.get("traced_steps"):
        return None
    ms = [s["dur_us"] / 1e3 for s in found[0]
          if s["name"] == span and program_spans.meets(s, where)]
    if not ms:
        return None
    if stat == "p50":
        return statistics.median(ms)
    return sum(ms) / result["traced_steps"]
