"""The program's own spans and scopes, for the readers that take a
per-layer metric from them (`mxnet_tpu.trace`, armed by the profiler
session of the traced stretch).

The buffer's stamps are `perf_counter` readings on the program's epoch;
the trace's are the profiler's. The program's `serve.step` (`train.step`)
span lies inside the harness's `bench.step` event, one to one, over the
traced stretch: `join()` takes the offset between the two clocks from
those pairs and `mapped()` puts the buffer's spans on the trace's clock,
as `xplane.Trace.host` tuples. Everything here returns None or nothing,
and never raises, where the program has no such span, scope or function
(the parent of the PR that added them has none).
"""
import statistics

HARNESS_STEP = "bench.step"
MAX_RESIDUAL_NS = 100_000
_said = set()


def spans():
    """The span records of the program's buffer, oldest first."""
    from mxnet_tpu import trace
    return trace.spans()


def scope_map(label):
    """{instruction name: op_name} of the newest executable the program
    noted under `label`, or {}."""
    from mxnet_tpu import trace
    build = getattr(trace, "scope_map", None)
    return build(label).get(label, {}) if build else {}


def start_ns(span):
    return span["ts_us"] * 1e3


def end_ns(span):
    return (span["ts_us"] + span["dur_us"]) * 1e3


def join(trace, program_spans, outer):
    """(offset_ns, residual_ns): what to add to a span's stamp to put it
    on the trace's clock, from the pairs (harness `bench.step` event,
    program span named `outer`) of the traced stretch: the median of
    their start differences. The residual is the largest distance by
    which a mapped `outer` span sticks out of its `bench.step`; above
    100 us the join is not good enough and this returns None."""
    if not trace or not trace.host:
        return None
    bench = [e for e in trace.host if e[0] == HARNESS_STEP]
    prog = [s for s in program_spans if s["name"] == outer][-len(bench):]
    if not bench or len(prog) != len(bench):
        return None
    # a span opens after its bench.step does, never before: of an even
    # count take the upper middle, the pair with the lesser delay
    offset = statistics.median_high(
        b[1] - start_ns(s) for b, s in zip(bench, prog))
    residual = max(
        max(b[1] - (start_ns(s) + offset),
            (end_ns(s) + offset) - (b[1] + b[2]), 0.0)
        for b, s in zip(bench, prog))
    if outer not in _said:
        _said.add(outer)
        print(f"program spans on the trace's clock: {len(bench)} pairs "
              f"({outer} in {HARNESS_STEP}), offset {offset / 1e9:.6f} s, "
              f"residual {residual / 1e3:.1f} us", flush=True)
    if residual > MAX_RESIDUAL_NS:
        return None
    return offset, residual


def mapped(program_spans, offset, names=None):
    """[(name, start_ns, duration_ns)] on the trace's clock, sorted by
    start, of the spans (named in `names`, or all)."""
    out = [(s["name"], start_ns(s) + offset, s["dur_us"] * 1e3)
           for s in program_spans if names is None or s["name"] in names]
    return sorted(out, key=lambda e: e[1])


def stretch(trace):
    """(start_ns, end_ns) of the traced stretch on the trace's clock:
    the harness's first span to its last."""
    return (min(e[1] for e in trace.host),
            max(e[1] + e[2] for e in trace.host))


def in_stretch(result, outer):
    """(program spans that start inside the traced stretch, offset_ns),
    or None where there is no trace, no span or no good join."""
    trace = result.get("trace")
    everything = spans()
    joined = join(trace, everything, outer)
    if joined is None:
        return None
    offset = joined[0]
    lo, hi = stretch(trace)
    return [s for s in everything
            if lo <= start_ns(s) + offset <= hi], offset


def meets(span, where):
    """Whether a span's attrs meet `where`: {attr: [op, value]} with op
    one of gt, eq, ge, lt. A span that lacks the attr does not."""
    for attr, (op, value) in (where or {}).items():
        got = span.get(attr)
        if got is None or not {"gt": got > value, "ge": got >= value,
                               "eq": got == value, "lt": got < value}[op]:
            return False
    return True


def self_time_events(events):
    """[(name, start_ns, self_ns)]: each event with its time less that of
    the events nested in it (`xplane.self_times`, kept per event)."""
    out, stack = [], []     # stack: [name, start, end, remaining self ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, _, left = stack.pop()
            out.append((name, start, max(left, 0)))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][3] -= dur
        stack.append([name, start, start + dur, dur])
    close(float("inf"))
    return out


def in_scopes(op_name, scopes):
    """Whether any component of a jax `op_name` path
    (`jit(step)/jvp(forward)/dot_general`) is one of `scopes`."""
    return any(part in scopes for part in op_name.split("/"))
