"""The one reduction from a profiler trace (.xplane.pb) to numbers.

`load()` turns jax's ProfileData into plain tuples; everything else works
on those, so the tests can hand it a trace built by hand. Times are in
nanoseconds as the trace gives them; results are in seconds.

What the trace of a v5e looks like (looked at by hand, PR 24): one plane
per chip named `/device:TPU:<n>` with the lines `Steps`, `XLA Modules`,
`XLA Ops` and `Async XLA Ops`. `XLA Ops` holds one event per executed HLO
instruction, named by the instruction's whole text (`%copy.602 = bf16[...]
copy(...)`; a Pallas kernel is a custom-call named after its `name=`, as
`%lamb_pass1.1` or `%transpose_jvp_flash_dkv__.14`), and a `while` as one
event around its body's events. `op_name()` cuts that text down to
`copy.602`. Host threads are lines of the plane `/host:CPU`;
`jax.profiler.TraceAnnotation` spans are events there, on the same clock.
"""
import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SMALL_GAP_NS = 5_000
SMALL_GAPS = "between_device_operations__under_5_us_each_"

Trace = collections.namedtuple("Trace", "devices host")
# devices: {plane name: [(name, start_ns, duration_ns), ...]} from OPS_LINE
# host:    [(name, start_ns, duration_ns), ...] of the harness's spans


def op_name(text):
    """`copy.602` from `%copy.602 = bf16[...] copy(...)`."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name):
    """`copy` from `copy.602`: the instruction without its number, so the
    instances of one kernel or one kind of operation add up. A plain
    `fusion.N` keeps its number: two of those have nothing in common."""
    base, dot, number = name.rpartition(".")
    if not dot or not number.isdigit() or base == "fusion":
        return name
    return base


def find(trace_dir):
    """The newest .xplane.pb under a jax.profiler log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path, span_prefix="bench."):
    """Trace from a file, or from serialized XSpace bytes."""
    from jax.profiler import ProfileData
    data = (ProfileData.from_serialized_xspace(path)
            if isinstance(path, bytes) else ProfileData.from_file(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(span_prefix))
    return Trace(devices, sorted(host, key=lambda e: e[1]))


def describe(path):
    """Planes, lines and event counts of a trace, for a look by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append((plane.name, line.name, len(events),
                        [e.name for e in events[:3]]))
    return out


def union(events):
    """Merged busy intervals [(start, end)] of events, sorted."""
    merged = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    per_chip = [sum(b - a for a, b in union(ev))
                for ev in trace.devices.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def self_times(events):
    """{name: ns} with each event's time less that of the events nested in
    it, so a `while` around a layer loop does not count its body twice."""
    out = collections.Counter()
    stack = []          # [name, end, remaining self ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, left = stack.pop()
            out[name] += max(left, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def seconds_by_name(trace):
    """{operation name: seconds of self time}, averaged over the chips."""
    total = collections.Counter()
    for events in trace.devices.values():
        total.update(self_times(events))
    n = max(len(trace.devices), 1)
    return {name: ns / n / 1e9 for name, ns in total.items()}


def seconds_matching(trace, needles):
    """Seconds of self time in operations whose name contains any of
    `needles`, averaged over the chips."""
    return sum(s for name, s in seconds_by_name(trace).items()
               if any(n in name for n in needles))


def idle_gaps(trace):
    """{what the host was doing: seconds of device idleness}, from the
    fullest chip's gaps between operations. A gap is named by the harness
    span open at its middle (the innermost one), `no_span` outside any;
    gaps under 5 us are lumped together."""
    if not trace.devices:
        return {}
    events = max(trace.devices.values(), key=len)
    busy = union(events)
    out = collections.Counter()
    for (_, end), (start, _) in zip(busy, busy[1:]):
        gap = start - end
        if gap < SMALL_GAP_NS:
            out[SMALL_GAPS] += gap
            continue
        mid = end + gap / 2
        label = "no_span"
        for name, s, d in trace.host:       # sorted by start: last wins
            if s > mid:
                break
            if s + d >= mid:
                label = name
        out[label] += gap
    return {k: v / 1e9 for k, v in out.items()}


def grouped(by_name):
    """{base name: seconds}: `seconds_by_name` with instances added up."""
    out = collections.Counter()
    for name, seconds in by_name.items():
        out[base_name(name)] += seconds
    return dict(out)


def top(mapping, n=10):
    """[[name, seconds], ...] the n largest, for the result's breakdown."""
    return [[k, v] for k, v in
            sorted(mapping.items(), key=lambda kv: -kv[1])[:n]]


class Recording:
    """The profiler on around a `with` block; `.trace` and `.seconds` (the
    block's length on the host clock) afterwards. Python-level tracing is
    off: it slows the host, and the harness's spans are TraceAnnotations.
    The files go to a temporary directory (under TMPDIR) and are removed
    once read, unless `keep` names a directory to leave them in."""

    def __init__(self, keep=None):
        self.keep = keep
        self.trace = None
        self.seconds = None

    def __enter__(self):
        import tempfile
        import time

        import jax
        self._dir = self.keep or tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._clock = time.perf_counter
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc):
        import shutil

        import jax
        self.seconds = self._clock() - self._t0
        jax.profiler.stop_trace()
        if exc[0] is None:
            self.trace = load(find(self._dir))
        if not self.keep:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
