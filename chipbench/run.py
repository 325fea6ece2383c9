#!/usr/bin/env python3
"""chipbench/run.py — one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by the names in BENCHMARK.json (configuration,
traffic mix, per-layer metrics and their readers: one file each, see
README.md), refuses anything but a TPU that peaks.json knows, hands the
cell to the driver of its configuration's `kind`, and prints the result as
one JSON object on the last line of stdout. With `--trace 0` the metrics
are the cell's end-to-end metrics; with `--trace 1` a short stretch after
the window runs under the profiler and the metrics are the per-layer ones.

`--rehearsal` is for the tests and for debugging the harness: the CPU, four
virtual devices, Pallas through the interpreter, the tiny sizes of each
file's `rehearsal` group. It prints counts only and no result line.
"""
import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEED_MOD = 2 ** 31 - 1      # the driver's seeds pass 32 signed bits
if ROOT not in sys.path:    # run as a script, sys.path[0] is chipbench/
    sys.path.insert(0, ROOT)


def say(msg):
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merged(base, over):
    """`base` with `over` laid on top, dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def cell_files(workload, rehearsal=False, root=ROOT):
    """(bench, cell, config, traffic) for a cell of BENCHMARK.json."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        sys.exit(f"chipbench: no workload {workload!r} in BENCHMARK.json; "
                 f"it has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(root, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearsal:
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    if traffic["chips"] != cell["chips"]:
        sys.exit(f"chipbench: {workload}: BENCHMARK.json says "
                 f"{cell['chips']} chips, the traffic file {traffic['chips']}")
    return bench, cell, config, traffic


def applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def require_chips(chips, rehearsal):
    """The devices the cell runs on and their peaks; exits unless jax found
    a TPU that peaks.json knows, with as many chips as the cell asks."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    say(f"jax {jax.__version__}  platform={dev.platform}  "
        f"device_kind={dev.device_kind!r}  count={len(devices)}")
    if rehearsal:
        return devices[:chips], None
    if os.environ.get("MXNET_TPU_PALLAS_INTERPRET") == "1":
        sys.exit("chipbench: MXNET_TPU_PALLAS_INTERPRET=1 would run every "
                 "kernel through the interpreter; unset it")
    if dev.platform != "tpu":
        sys.exit(f"chipbench: jax found platform {dev.platform!r}, not "
                 "'tpu'; nothing was run (use --rehearsal on a CPU)")
    table = load_json(HERE, "peaks.json")["by_device_kind"]
    if dev.device_kind not in table:
        sys.exit(f"chipbench: device_kind {dev.device_kind!r} is not in "
                 "chipbench/peaks.json; add it with its source")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, jax found "
                 f"{len(devices)}")
    return devices[:chips], table[dev.device_kind]


def memory_peak(stats):
    """Peak bytes on a chip from its allocator's statistics. The TPU runtime
    counts buffers (`peak_bytes_in_use`: weights, optimizer state, caches,
    batches) apart from what running programs reserve for their temporaries
    (`peak_bytes_reserved`: activations, padded copies); a chip holds both at
    once, so the peak is their sum. The two peaks need not fall in the same
    instant, so this can overstate by the buffers that set-up held and the
    steady state does not."""
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def layer_metrics(bench, workload, result):
    """{name: value} of the cell's per-layer metrics: each from the reader
    its own file names. A reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, workload):
            continue
        spec = load_json(HERE, "layer_metrics", metric["name"] + ".json")
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        value = reader.read(result, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def context(workload, seed, seconds, trace, rehearsal, steps=None,
            keep_trace=None, dump_steps=None):
    """(bench, ctx): BENCHMARK.json and what a kind's driver is handed.
    `steps` closes the window after a count of steps instead of `seconds`
    (the tests use it; the command has no such flag)."""
    bench, cell, config, traffic = cell_files(workload, rehearsal)
    devices, peaks = require_chips(cell["chips"], rehearsal)
    from mxnet_tpu import dataflow
    say("compile cache: " + dataflow.ensure_compile_cache())
    return bench, types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, devices=devices,
        peaks=peaks, seed=seed % SEED_MOD, seconds=seconds, trace=trace,
        rehearsal=rehearsal, steps=steps, keep_trace=keep_trace,
        dump_steps=dump_steps,
        t_start=T_START, say=say)


def driver_of(ctx):
    return importlib.import_module("chipbench.kinds." + ctx.config["kind"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="leave the profiler's files in DIR, to look at "
                         "a trace by hand (chipbench.xplane.describe)")
    ap.add_argument("--dump-steps", metavar="FILE", default=None,
                    help="serving kinds: write every step's time, width "
                         "and tokens to FILE (JSON), to ask where a spread "
                         "between runs comes from")
    args = ap.parse_args(argv)

    if args.rehearsal:      # before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
            + " --xla_force_host_platform_device_count=4"
        os.environ["MXNET_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

    bench, ctx = context(args.workload, args.seed, args.seconds,
                         bool(args.trace), args.rehearsal,
                         keep_trace=args.keep_trace,
                         dump_steps=args.dump_steps)
    devices = ctx.devices
    result = driver_of(ctx).run(ctx)

    if args.rehearsal:
        say(f"REHEARSAL {args.workload}: correct={result['correct']} "
            f"attempted={result['attempted']} failed={result['failed']}; "
            "no device, no result line")
        return 0 if result["correct"] else 1

    if args.trace:
        metrics = layer_metrics(bench, args.workload, result)
    else:
        metrics = {m["name"]: {"value": result["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if applies(m, args.workload)}
    dev = devices[0]
    fullest = max((d.memory_stats() for d in devices), key=memory_peak)
    say("memory of the fullest chip: " + ", ".join(
        f"{k}={v}" for k, v in sorted(fullest.items())))
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak(fullest)}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if args.trace:
        from chipbench import xplane
        trace = result["trace"]
        device["busy_s"] = xplane.busy_seconds(trace)
        device["window_s"] = result["traced_window_s"]
        ops = xplane.grouped(xplane.seconds_by_name(trace))
        steps = result["traced_steps"]
        say(f"device operations over {steps} traced steps, ms per step "
            "and chip: " + ", ".join(
                f"{k} {1e3 * v / steps:.3f}" for k, v in xplane.top(ops, 25)))
        line["breakdown"] = {
            "device_ops": xplane.top(ops),
            "idle_gaps": xplane.top(xplane.idle_gaps(trace))}
    # every number that decided `correct`, beside its limit: the line's last
    # key and the last lines of stderr, which is what the driver's record
    # keeps of a run that was not correct
    line["checks"] = {}
    for name, (value, limit) in result["checks"].items():
        line["checks"][name] = {"value": value, "limit": limit}
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
