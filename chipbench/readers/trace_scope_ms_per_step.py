"""Device self time, in ms per traced step and chip, of the operations
the program traced under the `jax.named_scope`s in `scopes`.

The trace names an event by its HLO instruction (`copy.602`);
`mxnet_tpu.trace.scope_map` is the program's map from there to the scope
path (`jit(pure)/while/body/kv_arena_update/scatter`), one map per
executable. The trace does not say which executable an event ran in, and
`copy.602` exists in several, so with `per_span` the events are told apart
by the clock join: those inside a program span named `per_span` (on the
trace's clock) ran the executable `label` names once the span's attrs are
put into it (`serve.paged/bucket={bucket}/chunk={chunk}`). Without
`per_span` the stretch ran one executable, `label`. Operations whose name
contains any of `exclude` are left out (a kernel that has its own
metric)."""
import bisect

from chipbench import program_spans


def read(result, scopes, label, outer, per_span=None, exclude=()):
    trace = result.get("trace")
    steps = result.get("traced_steps")
    if not trace or not trace.devices or not steps:
        return None
    scopes = set(scopes)

    def scoped(names, op):
        return program_spans.in_scopes(names.get(op, ""), scopes) \
            and not any(x in op for x in exclude)

    total = 0.0
    if per_span is None:
        names = program_spans.scope_map(label)
        if not names:
            return None
        for events in trace.devices.values():
            total += sum(ns for op, _, ns
                         in program_spans.self_time_events(events)
                         if scoped(names, op))
    else:
        found = program_spans.in_stretch(result, outer)
        if not found:
            return None
        try:
            windows = sorted(
                (program_spans.start_ns(s) + found[1],
                 program_spans.end_ns(s) + found[1], label.format(**s))
                for s in found[0] if s["name"] == per_span)
        except KeyError:        # a span without the attrs the label needs
            return None
        maps = {lab: program_spans.scope_map(lab)
                for lab in {w[2] for w in windows}}
        if not windows or not any(maps.values()):
            return None
        starts = [w[0] for w in windows]
        for events in trace.devices.values():
            for op, start, ns in program_spans.self_time_events(events):
                k = bisect.bisect_right(starts, start) - 1
                if k >= 0 and start <= windows[k][1] \
                        and scoped(maps[windows[k][2]], op):
                    total += ns
    if total <= 0:
        return None
    return total / len(trace.devices) / 1e6 / steps
