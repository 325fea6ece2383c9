#!/usr/bin/env python
"""Merge per-rank mx.trace span files into ONE clock-aligned Perfetto/
chrome trace and print a measured gang-wide verdict.

    python tools/trace_report.py TRACE_DIR
    python tools/trace_report.py diag/0/trace.jsonl diag/1/trace.jsonl
    python tools/trace_report.py TRACE_DIR --out merged.json --window 5

Input: `trace_dir/<rank>/trace.jsonl` files written by mx.trace (one meta
line carrying the rank's wall-clock epoch — and the shared gang epoch
when the gang was launched with `tools/launch.py --trace-dir` — then span
and skew records). Each rank's monotonic span timestamps are mapped onto
one absolute axis via its meta epoch, so the merged trace shows every
rank on the same timeline: one Perfetto process track per rank, one lane
per span category (step / input / compile / checkpoint).

Output:
  * `<dir>/trace_merged.json` (or --out): chrome://tracing / Perfetto
    JSON — load it in ui.perfetto.dev and read the gang like a score.
  * a per-window text verdict upgrading tools/telemetry_report.py's
    single-rank diagnosis to a measured gang-wide one:
      - **input-bound**    — some rank spends most of its busy time
        waiting on the input pipeline; names that straggler rank and its
        dominant span (batch wait vs H2D staging).
      - **comm-skew-bound** — the ranks' skew-probe arrival stamps at the
        collective boundary spread wider than a quarter of the mean step
        time: the gang serializes on the slowest arriver.
      - **compute-bound**  — otherwise; names the rank with the most
        step time (the critical-path rank) and its dominant span.
      - **compile-bound**  — a window with compile spans but no warm
        step spans (warmup): named as such instead of letting the
        nonzero batch wait during staging warmup masquerade as an
        input-bound straggler.

Cross-rank arrival skew is measured even when the workers never formed a
jax.distributed world: each rank's skew record wall-stamps its arrival at
the same sampled step, and the merge matches them by step id.

Reads only the stdlib so it runs anywhere the files land (no jax);
malformed lines are skipped, not fatal. Exits 2 on no input files.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _rankfiles import discover_rank_files  # noqa: E402

# Perfetto lane (tid) per span category, so each rank's track splits into
# stable sub-lanes instead of interleaving unrelated spans on one row
_TID = {"step": 0, "input": 1, "compile": 2, "checkpoint": 3, "host": 4}
_TID_OTHER = 9

#: arrival spread above this fraction of the mean step time flips the
#: verdict to comm-skew-bound (a quarter step lost per collective is the
#: point where the skew, not the math, owns the step time)
SKEW_FRACTION = 0.25


def discover(paths):
    """[(rank, path)] from a trace dir (numbered subdirs) or explicit
    files (rank from the nearest all-digit path component, else order)."""
    return discover_rank_files(paths, "trace.jsonl", tool="trace_report")


def load(path):
    """(meta, spans, skews) from one rank file; bad lines skipped.

    A relaunched worker generation (launch.py --max-restarts) re-opens
    the same file in append mode and writes a NEW meta line with its own
    monotonic epoch — its spans' ts_us restart near zero. Records after
    a later meta are rebased onto the FIRST meta's epoch (via the wall-
    clock delta between the two epochs), so every generation lands at
    its true position on one axis instead of overlapping generation 1."""
    meta, spans, skews = None, [], []
    rebase_us = 0.0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # half-written tail from a killed flush
            if not isinstance(rec, dict):
                continue
            kind = rec.get("kind")
            if kind == "meta":
                if meta is None:
                    meta = rec
                else:
                    try:
                        rebase_us = (int(rec["epoch_unix_ns"])
                                     - int(meta["epoch_unix_ns"])) / 1e3
                    except (KeyError, TypeError, ValueError):
                        pass  # keep the previous rebase
            elif kind in ("span", "skew"):
                if rebase_us and "ts_us" in rec:
                    rec = dict(rec, ts_us=rec["ts_us"] + rebase_us)
                (spans if kind == "span" else skews).append(rec)
    return meta, spans, skews


def _offsets_us(ranks):
    """Per-rank offset (µs) mapping each rank's monotonic span clock onto
    one shared absolute axis: the earliest rank epoch (or the shared gang
    epoch, when every meta carries the same one) is time zero."""
    epochs = {}
    for rank, (meta, _spans, _skews) in ranks.items():
        e = (meta or {}).get("epoch_unix_ns")
        epochs[rank] = int(e) if e is not None else None
    known = [e for e in epochs.values() if e is not None]
    ref = min(known) if known else 0
    gangs = {(m or {}).get("gang_epoch_ns")
             for m, _s, _k in ranks.values()}
    gang = gangs.pop() if len(gangs) == 1 else None
    if gang is not None and known:
        ref = min(ref, int(gang))
    return {rank: ((e - ref) / 1e3 if e is not None else 0.0)
            for rank, e in epochs.items()}, ref


def merge_chrome(ranks, offsets):
    """The merged chrome-trace document: one process per rank, one lane
    per span category, skew probes as instant events."""
    events = []
    for rank in sorted(ranks):
        events.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0, "args": {"name": f"rank {rank}"}})
        for cat, tid in sorted(_TID.items(), key=lambda kv: kv[1]):
            events.append({"name": "thread_name", "ph": "M", "pid": rank,
                           "tid": tid, "args": {"name": cat}})
        off = offsets[rank]
        _meta, spans, skews = ranks[rank]
        for s in spans:
            args = {k: s[k] for k in ("step", "block") if k in s}
            events.append({
                "name": s.get("name", "?"),
                "cat": s.get("cat", "host"), "ph": "X",
                "ts": round(off + float(s.get("ts_us", 0.0)), 1),
                "dur": round(float(s.get("dur_us", 0.0)), 1),
                "pid": rank, "tid": _TID.get(s.get("cat"), _TID_OTHER),
                "args": args,
            })
        for k in skews:
            events.append({
                "name": "skew_probe", "ph": "i", "s": "p",
                "ts": round(off + float(k.get("ts_us", 0.0)), 1),
                "pid": rank, "tid": _TID["step"],
                "args": {kk: k[kk] for kk in
                         ("step", "spread_s", "straggler_rank",
                          "participants") if kk in k},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _percentile(samples, q):
    if not samples:
        return None
    s = sorted(samples)
    return s[min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1))))]


def cross_rank_skews(ranks):
    """Measured arrival spread per sampled step, matched ACROSS ranks by
    (relaunch generation, step id) from the wall-stamped skew records:
    [(step, spread_s, straggler_rank)]. Works without any collective
    having run. Matching within a generation matters: a resumed gang
    replays step ids, and pairing a survivor's replayed stamp with a
    dead rank's pre-restart stamp would read the restart backoff —
    seconds to minutes — as arrival skew and flip the verdict."""
    by_step = {}
    for rank, (_meta, _spans, skews) in ranks.items():
        for k in skews:
            if "t_wall_ns" in k and "step" in k:
                key = (int(k.get("gen", 0)), int(k["step"]))
                by_step.setdefault(key, {})[rank] = int(k["t_wall_ns"])
    out = []
    for (_gen, step), stamps in sorted(by_step.items()):
        if len(stamps) < 2:
            continue
        t_min = min(stamps.values())
        straggler = max(stamps, key=stamps.get)
        out.append((step, (max(stamps.values()) - t_min) / 1e9, straggler))
    return out


def _window_stats(ranks, offsets, lo_us, hi_us):
    """Per-rank span-time aggregation restricted to [lo_us, hi_us) on the
    shared axis: {"by_cat": {cat: us}, "by_span": {name: us}, "steps":
    [step dur_us], "rungs": {width: [passes, tokens fed]} of the window's
    serving passes, "fetched": [bytes, logits rows, scheduler steps] that
    those passes copied to the host} per rank."""
    stats = {}
    for rank, (_meta, spans, _skews) in ranks.items():
        off = offsets[rank]
        by_cat, by_span, step_us = {}, {}, {}
        rungs = {}      # width -> [passes, tokens fed in them]
        fetched = [0, 0, 0]
        for s in spans:
            ts = off + float(s.get("ts_us", 0.0))
            if not (lo_us <= ts < hi_us):
                continue
            dur = float(s.get("dur_us", 0.0))
            cat = s.get("cat", "host")
            name = s.get("name", "?")
            by_cat[cat] = by_cat.get(cat, 0.0) + dur
            by_span[name] = by_span.get(name, 0.0) + dur
            if cat == "step" and "step" in s:
                step_us[s["step"]] = step_us.get(s["step"], 0.0) + dur
            if name == "serve.decode_step" and "width" in s:
                rung = rungs.setdefault(int(s["width"]), [0, 0])
                rung[0] += 1
                rung[1] += int(s.get("fed", 0))
            if name == "serve.fetch" and "bytes" in s:
                fetched[0] += int(s["bytes"])
                fetched[1] += int(s.get("rows", 0))
            if name == "serve.step":
                fetched[2] += 1
        stats[rank] = {"by_cat": by_cat, "by_span": by_span,
                       "steps": sorted(step_us.values()), "rungs": rungs,
                       "fetched": fetched}
    return stats


def _verdict(stats, skews_in_window):
    """(kind, straggler_rank, dominant_span, detail) for one window."""
    # serving windows first: a rank whose window holds mx.serve spans but
    # no train steps is an inference server — the question there is not
    # input- vs compute-bound but where a request's latency went: waiting
    # in the admission queue (queue-bound: add slots/capacity or shed
    # earlier) or in the batched decode dispatch (decode-bound: the
    # model/hardware is the floor)
    serve_frac = {}
    for rank, st in stats.items():
        if st["by_cat"].get("step") or not st["by_cat"].get("serve"):
            continue
        qwait = st["by_span"].get("serve.queue_wait", 0.0)
        decode = st["by_span"].get("serve.decode_step", 0.0)
        if qwait + decode > 0:
            serve_frac[rank] = (qwait / (qwait + decode), qwait, decode)
    if serve_frac:
        rank = max(serve_frac, key=lambda r: serve_frac[r][0])
        frac, qwait, decode = serve_frac[rank]
        # the fill of the passes: a step's tokens go through the layer
        # stack in the fewest passes the ladder of widths allows, each
        # padded to the narrowest rung that holds it (`width`/`fed` on
        # serve.decode_step)
        nbytes, lrows, steps = stats[rank].get("fetched", (0, 0, 0))
        rungs = stats[rank].get("rungs")
        fill = ""
        if rungs:
            width = sum(w * n for w, (n, _) in rungs.items())
            fed = sum(f for _, f in rungs.values())
            passes = sum(n for n, _ in rungs.values())
            fill = f"; passes {fed / width:.1%} full ({fed} tokens in " \
                f"{width} virtual rows); {passes / max(steps, 1):.2f} " \
                "passes a step, by rung: " + ", ".join(
                    f"{w} x {n} {f / (w * n):.0%} full"
                    for w, (n, f) in sorted(rungs.items()))
        # what the passes copied to the host (`bytes`/`rows` on
        # serve.fetch): token ids, and logits rows only where a request
        # samples or keeps them
        if nbytes:
            fill += f"; fetched {nbytes / max(steps, 1):,.0f} bytes a " \
                f"step ({lrows} logits rows in {steps} steps)"
        if frac > 0.5:
            return ("queue-bound", rank, "serve.queue_wait",
                    f"{frac:.1%} of request time waiting for admission "
                    f"({qwait / 1e6:.3f}s queued vs {decode / 1e6:.3f}s "
                    "decoding) — add slots/capacity or shed earlier"
                    + fill)
        return ("decode-bound", rank, "serve.decode_step",
                f"{1 - frac:.1%} of request time in batched decode "
                f"({decode / 1e6:.3f}s decoding vs {qwait / 1e6:.3f}s "
                "queued) — the model/hardware is the latency floor"
                + fill)
    input_frac = {}
    for rank, st in stats.items():
        # only the CONSUMER-visible stall counts as input waiting:
        # input.h2d_stage runs in the prefetch worker thread overlapped
        # with device compute — a long stage span that never surfaces as
        # batch_wait means the overlap WORKED (dataflow.py documents
        # exactly this), so summing the whole input category would call
        # a healthy pipeline input-bound
        inp = st["by_span"].get("input.batch_wait", 0.0)
        # compile time counts in the denominator: a warmup window whose
        # steps were all cache misses has by_cat['step'] == 0 (they
        # record step.compile instead), and any nonzero batch_wait would
        # otherwise make input_frac == 1.0 — a compile-dominated window
        # is compile-bound, not input-bound
        busy = st["by_cat"].get("step", 0.0) \
            + st["by_cat"].get("compile", 0.0)
        if inp + busy > 0:
            input_frac[rank] = inp / (inp + busy)
    all_steps = [d for st in stats.values() for d in st["steps"]]
    mean_step_s = (sum(all_steps) / len(all_steps) / 1e6) if all_steps \
        else None
    if input_frac and max(input_frac.values()) > 0.5:
        rank = max(input_frac, key=input_frac.get)
        spans = {n: d for n, d in stats[rank]["by_span"].items()
                 if n.startswith("input.")}
        dom = max(spans, key=spans.get) if spans else "input"
        return ("input-bound", rank, dom,
                f"{input_frac[rank]:.1%} of rank-busy time waiting on "
                f"input ({spans.get(dom, 0.0) / 1e6:.3f}s in {dom})")
    spreads = [sp for _step, sp, _r in skews_in_window]
    if spreads and mean_step_s:
        p99 = _percentile(spreads, 99)
        if p99 > SKEW_FRACTION * mean_step_s:
            stragglers = [r for _step, _sp, r in skews_in_window]
            mode = max(set(stragglers), key=stragglers.count)
            return ("comm-skew-bound", mode, "collective arrival",
                    f"arrival spread p99 {p99 * 1e3:.2f} ms vs mean step "
                    f"{mean_step_s * 1e3:.2f} ms — the gang serializes "
                    "on the slowest arriver")
    busy = {rank: st["by_cat"].get("step", 0.0)
            for rank, st in stats.items() if st["by_cat"].get("step")}
    if not busy:
        comp = {rank: st["by_cat"].get("compile", 0.0)
                for rank, st in stats.items()
                if st["by_cat"].get("compile")}
        if comp:
            rank = max(comp, key=comp.get)
            spans = {n: d for n, d in stats[rank]["by_span"].items()
                     if n in ("compile", "step.compile")}
            dom = max(spans, key=spans.get) if spans else "compile"
            return ("compile-bound", rank, dom,
                    f"all step time in this window was jit compilation "
                    f"({comp[rank] / 1e6:.3f}s on rank {rank}) — warmup, "
                    "not steady state")
        return ("idle", None, None, "no step spans in this window")
    rank = max(busy, key=busy.get)
    # dominant span from the step category only — a one-off compile span
    # must not masquerade as the steady-state critical path
    spans = {n: d for n, d in stats[rank]["by_span"].items()
             if n in ("step.dispatch", "step.fence")}
    dom = max(spans, key=spans.get) if spans else "step"
    return ("compute-bound", rank, dom,
            f"critical-path rank by step time "
            f"({busy[rank] / 1e6:.3f}s; dominant span {dom})")


def report(ranks, offsets, window_s=None):
    """The text report: per-rank summaries, measured arrival skew, and
    the per-window gang verdict lines."""
    lines = [f"trace report: {len(ranks)} rank(s)", "=" * 60]
    all_ts = []
    for rank in sorted(ranks):
        off = offsets[rank]
        _meta, spans, skews = ranks[rank]
        for s in spans:
            all_ts.append(off + float(s.get("ts_us", 0.0)))
            all_ts.append(off + float(s.get("ts_us", 0.0))
                          + float(s.get("dur_us", 0.0)))
        steps = {}
        for s in spans:
            if s.get("cat") == "step" and "step" in s:
                steps[s["step"]] = steps.get(s["step"], 0.0) \
                    + float(s.get("dur_us", 0.0))
        durs = sorted(steps.values())
        cats = {}
        for s in spans:
            cats[s.get("cat", "host")] = cats.get(s.get("cat", "host"),
                                                  0.0) \
                + float(s.get("dur_us", 0.0))
        catstr = "  ".join(f"{c} {u / 1e6:.3f}s"
                           for c, u in sorted(cats.items()))
        if durs:
            lines.append(
                f"  rank {rank}: {len(durs)} sampled steps  "
                f"p50 {_percentile(durs, 50) / 1e3:.2f} ms  "
                f"p99 {_percentile(durs, 99) / 1e3:.2f} ms  |  {catstr}")
        else:
            lines.append(f"  rank {rank}: no step spans  |  {catstr}")
    skews = cross_rank_skews(ranks)
    if skews:
        spreads = [sp for _s, sp, _r in skews]
        stragglers = [r for _s, _sp, r in skews]
        mode = max(set(stragglers), key=stragglers.count)
        lines.append(
            f"  arrival skew: {len(skews)} matched probes  "
            f"p50 {_percentile(spreads, 50) * 1e3:.2f} ms  "
            f"p99 {_percentile(spreads, 99) * 1e3:.2f} ms  "
            f"most-frequent straggler rank {mode}")
    if not all_ts:
        lines.append("no spans recorded")
        return "\n".join(lines)
    lo, hi = min(all_ts), max(all_ts) + 1.0
    win_us = window_s * 1e6 if window_s else (hi - lo)
    w = 0
    start = lo
    while start < hi:
        end = start + win_us
        stats = _window_stats(ranks, offsets, start, end)
        in_win = skews
        if window_s:
            # restrict matched skews to probes whose span timestamps fall
            # inside this window (matched per rank; use any rank's stamp)
            steps_in = set()
            for rank in ranks:
                off = offsets[rank]
                for k in ranks[rank][2]:
                    ts = off + float(k.get("ts_us", 0.0))
                    if start <= ts < end and "step" in k:
                        steps_in.add(int(k["step"]))
            in_win = [(s, sp, r) for (s, sp, r) in skews if s in steps_in]
        kind, rank, dom, detail = _verdict(stats, in_win)
        span_txt = f" (dominant span {dom})" if dom and kind != \
            "compute-bound" else ""
        who = f" — straggler rank {rank}" if rank is not None else ""
        lines.append(
            f"window {w} [+{(start - lo) / 1e6:.3f}s .. "
            f"+{(end - lo) / 1e6:.3f}s]: verdict: {kind}{who}"
            f"{span_txt}: {detail}")
        w += 1
        start = end
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merge per-rank mx.trace files into one clock-aligned "
        "Perfetto trace and print the gang-wide straggler verdict")
    ap.add_argument("paths", nargs="+",
                    help="a trace_dir (numbered rank subdirs) or explicit "
                         "per-rank trace.jsonl files")
    ap.add_argument("--out", default=None,
                    help="merged chrome-trace JSON path (default: "
                         "<trace_dir>/trace_merged.json, or "
                         "trace_merged.json beside the first file)")
    ap.add_argument("--window", type=float, default=None,
                    help="verdict window in seconds (default: one window "
                         "over the whole run)")
    args = ap.parse_args(argv)

    files = discover(args.paths)
    if not files:
        print(f"trace_report: no trace.jsonl files under {args.paths}",
              file=sys.stderr)
        return 2
    ranks = {}
    for rank, path in files:
        ranks[rank] = load(path)
    offsets, _ref = _offsets_us(ranks)

    out = args.out
    if out is None:
        base = args.paths[0] if os.path.isdir(args.paths[0]) \
            else os.path.dirname(os.path.dirname(files[0][1])) or "."
        out = os.path.join(base, "trace_merged.json")
    doc = merge_chrome(ranks, offsets)
    with open(out, "w") as f:
        json.dump(doc, f)
    print(f"wrote {out} ({len(doc['traceEvents'])} events, "
          f"{len(ranks)} rank tracks)")
    print(report(ranks, offsets, window_s=args.window))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
