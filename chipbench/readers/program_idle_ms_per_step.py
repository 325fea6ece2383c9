"""Device idleness, in ms per traced step, inside the program's own spans
named in `spans`: the gaps of at least 5 us between operations of the
fullest chip (`xplane.idle_gaps`) whose middle lies in such a span, once
the spans are on the trace's clock (`program_spans.join` over `outer`)."""
from chipbench import program_spans, xplane


def read(result, spans, outer):
    found = program_spans.in_stretch(result, outer)
    trace = result.get("trace")
    if not found or not trace.devices or not result.get("traced_steps"):
        return None
    host = program_spans.mapped(found[0], found[1], set(spans))
    if not host:
        return None
    gaps = xplane.idle_gaps(xplane.Trace(trace.devices, host))
    seconds = sum(gaps.get(name, 0.0) for name in spans)
    return 1e3 * seconds / result["traced_steps"]
