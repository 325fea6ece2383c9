#!/usr/bin/env python
"""Summarize telemetry JSONL runs (mx.telemetry.dump_jsonl output, or
telemetry_jsonl_path auto-flush files).

    python tools/telemetry_report.py run.jsonl
    python tools/telemetry_report.py diag/0/run.jsonl diag/1/run.jsonl

With several files (one per rank — e.g. each worker pointing
telemetry_jsonl_path into its tools/launch.py rank dir), every file gets a
rank-labelled section plus a cross-rank summary naming the slowest rank by
step p99. Rank labels come from the nearest all-digit path component
(`diag/3/run.jsonl` → rank 3), falling back to argument order.

Per file prints: recompile count with per-event causes, step-time p50/p99,
a "cost & efficiency" section when mx.inspect cost events are present (top
executables by device memory, flops / arithmetic intensity / roofline, MFU
against the recorded per-chip peak, estimated collective-traffic share,
and a one-line input/comm/compute-bound verdict), a "serve:" section when
the run served traffic (requests by outcome, token throughput, TTFT and
queue-wait p50/p99, shed/deadline-miss/degradation counts), an "slo:"
section when mx.slo classified requests (good/bad counts, error-budget
burn rate per window with the worst window named, the top violated
objective, alert history), collective/
kvstore bytes moved, and the input-stall fraction (time blocked on the
input pipeline as a share of run time) — the triage order for a slow TPU
training run: recompiling? input-bound? comms-bound? only then look at
the kernels (mx.profiler / jax.profiler).

Reads only the stdlib so it runs anywhere the JSONL lands (no jax import);
malformed lines and records with missing fields are skipped, not fatal.
"""
import json
import os
import sys


def load(path):
    events, snapshot = [], {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except ValueError:
                continue  # half-written line from a crashed flush
            if not isinstance(ev, dict):
                continue
            if ev.get("kind") == "snapshot":
                snapshot = ev.get("metrics", {})  # last snapshot wins
            else:
                events.append(ev)
    return events, snapshot


def percentile(samples, q):
    if not samples:
        return None
    s = sorted(samples)
    return s[min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1))))]


def _metric_sum(snapshot, name):
    """Histogram sum / counter value for `name`, summed over labels."""
    m = snapshot.get(name)
    if not m:
        return 0.0
    if "labels" in m:
        return sum(c.get("sum", c.get("value", 0.0)) or 0.0
                   for c in m["labels"].values())
    return m.get("sum", m.get("value", 0.0)) or 0.0


def _label_values(snapshot, name):
    m = snapshot.get(name, {})
    out = {k: c.get("value", 0.0)
           for k, c in m.get("labels", {}).items()}
    if not out and m.get("value"):
        out[""] = m["value"]
    return out


def fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


def _cost_records(events):
    """Latest mx.inspect `cost` event per executable (later compiles of
    the same executable supersede earlier ones)."""
    recs = {}
    for e in events:
        if e.get("kind") == "cost" and e.get("executable"):
            recs[e["executable"]] = e
    return recs


def _cost_efficiency(events, step_p50):
    """The "Cost & efficiency" lines plus (mfu, comm_share) for the
    verdict: top executables by device memory, per-executable flops /
    arithmetic intensity / roofline, MFU of the hottest (most-flops)
    executable against the per-backend peak recorded in the event, and
    the estimated collective traffic share of all bytes moved. Every
    input is nullable (CPU backends report flops but little else) —
    missing pieces drop out of the lines rather than crashing."""
    recs = _cost_records(events)
    if not recs:
        return [], None, None
    lines = ["cost:"]
    by_mem = sorted([r for r in recs.values()
                     if isinstance(r.get("peak_bytes"), (int, float))],
                    key=lambda r: -r["peak_bytes"])
    for r in by_mem[:3]:
        parts = [f"args {fmt_bytes(r['argument_bytes'])}"
                 if isinstance(r.get("argument_bytes"), (int, float)) else "",
                 f"temp {fmt_bytes(r['temp_bytes'])}"
                 if isinstance(r.get("temp_bytes"), (int, float)) else "",
                 f"donated {fmt_bytes(r['donated_bytes'])}"
                 if isinstance(r.get("donated_bytes"), (int, float)) else ""]
        detail = ", ".join(p for p in parts if p)
        lines.append(f"  {r['executable']}: peak device memory "
                     f"{fmt_bytes(r['peak_bytes'])}"
                     + (f" ({detail})" if detail else ""))
    mfu = None
    hot = max((r for r in recs.values()
               if isinstance(r.get("flops"), (int, float))),
              key=lambda r: r["flops"], default=None)
    if hot is not None:
        desc = f"  {hot['executable']}: {hot['flops'] / 1e9:.3f} GFLOP/step"
        ba = hot.get("bytes_accessed")
        if isinstance(ba, (int, float)) and ba:
            ai = hot["flops"] / ba
            desc += f", arithmetic intensity {ai:.1f} FLOP/B"
            peak, bw = hot.get("peak_flops"), hot.get("peak_bandwidth")
            if peak and bw:
                bound = "compute-bound" if ai >= peak / bw \
                    else "memory-bound"
                desc += f" ({bound})"
        peak = hot.get("peak_flops")
        if peak and step_p50:
            mfu = hot["flops"] / step_p50 / peak
            desc += (f", MFU {mfu:.1%} of {peak / 1e12:.0f} TFLOP/s peak "
                     f"@ p50 step")
        lines.append(desc)
    agg_ops = {}
    for r in recs.values():
        for op, b in (r.get("collectives") or {}).items():
            if isinstance(b, (int, float)):
                agg_ops[op] = agg_ops.get(op, 0) + b
    comm = sum(agg_ops.values())
    comm_share = None
    if comm:
        total_accessed = sum(r["bytes_accessed"] for r in recs.values()
                             if isinstance(r.get("bytes_accessed"),
                                           (int, float)))
        ops = ", ".join(f"{op} {fmt_bytes(b)}/step"
                        for op, b in sorted(agg_ops.items()))
        line = f"  est. collective traffic: {ops}"
        if total_accessed:
            comm_share = comm / (comm + total_accessed)
            line += f" — {comm_share:.1%} of bytes moved"
        lines.append(line)
    return lines, mfu, comm_share


def _metric_percentiles(snapshot, name):
    """(p50, p99, count) of a snapshot histogram (None-safe)."""
    m = snapshot.get(name) or {}
    return m.get("p50"), m.get("p99"), m.get("count") or 0


def _serve_section(events, snapshot):
    """The "serve:" lines (PR 12 recorded the serve_* series; this
    renders them): requests by terminal outcome, token throughput, TTFT
    and queue-wait percentiles, and the overload counters (shed /
    deadline-miss / degradations). Empty when the run never served."""
    outcomes = _label_values(snapshot, "serve_requests_total")
    tokens = _metric_sum(snapshot, "serve_tokens_total")
    ttft_p50, ttft_p99, ttft_n = _metric_percentiles(
        snapshot, "serve_ttft_seconds")
    total = sum(outcomes.values())
    # gate on recorded VALUES, not registered series: importing mx.serve
    # registers zero-valued children, and a training run's report must
    # not grow a phantom all-zero serving section from that
    if not total and not tokens and not ttft_n:
        return []
    lines = ["serve:"]
    by_outcome = ", ".join(
        f"{k.split('=')[-1].strip(chr(34) + '{}')} {int(v)}"
        for k, v in sorted(outcomes.items())) or "none"
    lines.append(f"  requests:   {int(total)} ({by_outcome})")
    tok_line = f"  tokens:     {int(tokens)}"
    # throughput needs a wall span: the serve events (degradations) and
    # step/compile events all carry ts — use the run's event span when
    # it is meaningful, else report the total alone
    stamps = [e["ts"] for e in events
              if isinstance(e.get("ts"), (int, float))]
    if tokens and len(stamps) >= 2 and max(stamps) - min(stamps) > 0.1:
        tok_line += (f", {tokens / (max(stamps) - min(stamps)):.1f}"
                     " tokens/s over the event span")
    lines.append(tok_line)
    if ttft_n:
        lines.append(
            f"  ttft:       p50 {(ttft_p50 or 0) * 1e3:.1f} ms  "
            f"p99 {(ttft_p99 or 0) * 1e3:.1f} ms  ({int(ttft_n)} first "
            "tokens)")
    qw_p50, qw_p99, qw_n = _metric_percentiles(
        snapshot, "serve_queue_wait_seconds")
    if qw_n:
        lines.append(f"  queue wait: p50 {(qw_p50 or 0) * 1e3:.1f} ms  "
                     f"p99 {(qw_p99 or 0) * 1e3:.1f} ms")
    shed = outcomes.get('{outcome="shed"}', 0)
    rejected = outcomes.get('{outcome="rejected"}', 0)
    missed = _metric_sum(snapshot, "serve_deadline_missed_total")
    degraded = _metric_sum(snapshot, "serve_degraded_total")
    if shed or rejected or missed or degraded:
        lines.append(f"  overload:   shed {int(shed)}, rejected "
                     f"{int(rejected)}, deadline-missed {int(missed)}, "
                     f"degradations {int(degraded)}")
    return lines


def _slo_section(events, snapshot):
    """The "slo:" lines (mx.slo's error-budget view of the same serving
    run): good/bad classifications, burn rate per window with the worst
    window called out, the top violated objective, and the alert
    history. Empty when nothing was classified — importing mx.slo
    registers zero-valued series, and a run that never served must not
    grow a phantom SLO section."""
    verdicts = _label_values(snapshot, "slo_requests_total")
    classified = sum(verdicts.values())
    alerts = [e for e in events if e.get("kind") == "slo_alert"]
    if not classified and not alerts:
        return []
    lines = ["slo:"]
    bad = sum(v for k, v in verdicts.items() if '"bad"' in k)
    lines.append(f"  classified: {int(classified)} requests, "
                 f"{int(bad)} bad")
    burns = _label_values(snapshot, "slo_burn_rate")
    if burns:
        per = ", ".join(
            f"{k.split('=')[-1].strip(chr(34) + '{}')} x{v:.2f}"
            for k, v in sorted(burns.items()))
        worst = max(burns, key=lambda k: burns[k])
        worst_name = worst.split('=')[-1].strip(chr(34) + '{}')
        lines.append(f"  burn rate:  {per} — worst window: {worst_name} "
                     f"(x{burns[worst]:.2f} the sustainable rate"
                     + (", budget burning)" if burns[worst] >= 1.0
                        else ")"))
    viol = _label_values(snapshot, "slo_violations_total")
    viol = {k: v for k, v in viol.items() if v}
    if viol:
        top = max(viol, key=lambda k: viol[k])
        top_name = top.split('=')[-1].strip(chr(34) + '{}')
        by = ", ".join(
            f"{k.split('=')[-1].strip(chr(34) + '{}')} {int(v)}"
            for k, v in sorted(viol.items()))
        lines.append(f"  violations: {by} — top violated objective: "
                     f"{top_name}")
    n_alerts = _metric_sum(snapshot, "slo_alerts_total")
    if alerts or n_alerts:
        first = alerts[0] if alerts else None
        line = f"  alerts:     {int(n_alerts or len(alerts))} fired"
        if first is not None:
            line += (f" — first: window={first.get('window')} "
                     f"burn=x{first.get('burn', 0):.2f}")
        lines.append(line)
    return lines


def report(path, label=None, data=None):
    events, snapshot = data if data is not None else load(path)
    title = f"telemetry report: {path}" if label is None \
        else f"telemetry report [{label}]: {path}"
    lines = [title, "=" * 60]

    # -- compiles / recompiles -------------------------------------------
    compiles = [e for e in events if e.get("kind") == "compile"]
    recompiles = [e for e in events if e.get("kind") == "recompile"]
    compile_s = _metric_sum(snapshot, "compile_seconds")
    lines.append(f"compiles:   {len(compiles)} first-time, "
                 f"{len(recompiles)} recompiles, "
                 f"{compile_s:.2f}s total compile time")
    cache_hits = _metric_sum(snapshot, "compile_cache_hits_total")
    cache_misses = _metric_sum(snapshot, "compile_cache_misses_total")
    if cache_hits or cache_misses:
        # persistent XLA cache (dataflow.ensure_compile_cache): hits
        # deserialized an executable instead of rebuilding it — warm, not cold, compiles
        lines.append(f"  persistent cache: {int(cache_hits)} warm hits, "
                     f"{int(cache_misses)} cold misses")
    for e in recompiles:
        causes = "; ".join(e.get("causes", [])) or "unknown"
        lines.append(f"  recompile {e.get('block', '?')}: {causes} "
                     f"({(e.get('compile_time_s') or 0):.2f}s)")

    # -- step time --------------------------------------------------------
    steps = [e["dur_s"] for e in events
             if e.get("kind") == "step"
             and isinstance(e.get("dur_s"), (int, float))]
    if steps:
        p50, p99 = percentile(steps, 50), percentile(steps, 99)
        lines.append(f"steps:      {len(steps)}  "
                     f"p50 {p50 * 1e3:.2f} ms  p99 {p99 * 1e3:.2f} ms")
    else:
        h = snapshot.get("trainer_step_seconds", {})
        if h.get("count"):
            lines.append(
                f"steps:      {h['count']}  "
                f"p50 {(h.get('p50') or 0) * 1e3:.2f} ms  "
                f"p99 {(h.get('p99') or 0) * 1e3:.2f} ms (from snapshot)")
        else:
            lines.append("steps:      none recorded")

    # -- cost & efficiency (mx.inspect cost events) -----------------------
    step_p50 = percentile(steps, 50) if steps else \
        snapshot.get("trainer_step_seconds", {}).get("p50")
    cost_lines, mfu, comm_share = _cost_efficiency(events, step_p50)
    lines.extend(cost_lines)

    # -- serving (mx.serve serve_* series) --------------------------------
    lines.extend(_serve_section(events, snapshot))

    # -- SLO error budget (mx.slo slo_* series) ---------------------------
    lines.extend(_slo_section(events, snapshot))

    # -- comms ------------------------------------------------------------
    coll = _label_values(snapshot, "collective_bytes_total")
    kv = _label_values(snapshot, "kvstore_bytes_total")
    total_comms = sum(coll.values()) + sum(kv.values())
    lines.append(f"comms:      {fmt_bytes(total_comms)} total")
    for tag, vals in (("collective", coll), ("kvstore", kv)):
        for k, v in sorted(vals.items()):
            lines.append(f"  {tag}{k}: {fmt_bytes(v)}")

    # -- input pipeline ---------------------------------------------------
    host_wait = _metric_sum(snapshot, "dataloader_wait_seconds")
    dev_wait = _metric_sum(snapshot, "device_prefetch_wait_seconds")
    dev_present = bool(snapshot.get("device_prefetch_wait_seconds",
                                    {}).get("count"))
    # with prefetch_to_mesh in the pipeline, the host DataLoader is
    # consumed by the PREFETCH WORKER — its waits overlap device compute
    # and are producer-side, not consumer stalls; only the staging wait
    # blocks the train loop. Without a device stage, host wait IS the
    # consumer stall.
    wait_s = dev_wait if dev_present else host_wait
    step_s = sum(steps) if steps else _metric_sum(snapshot,
                                                  "trainer_step_seconds")
    denom = wait_s + step_s
    if denom > 0:
        frac = wait_s / denom
        verdict = "input-bound" if frac > 0.5 else "compute-bound"
        lines.append(f"input:      {wait_s:.2f}s waiting on batches, "
                     f"stall fraction {frac:.1%} ({verdict})")
        if mfu is not None:
            # one verdict that folds MFU in, printed NEXT to the stall
            # attribution and derived from the same stall fraction, so the
            # two diagnoses can never silently disagree
            kind = "input-bound" if frac > 0.5 else \
                "comm-bound" if (comm_share or 0.0) > 0.5 else \
                "compute-bound"
            lines.append(
                f"  verdict: {kind}, MFU={mfu:.1%}"
                + (f", comm share {comm_share:.1%}"
                   if comm_share is not None else ""))
        if dev_present:
            # two-stage attribution: host batch production (DataLoader
            # workers, overlapped) vs H2D staging (prefetch_to_mesh, the
            # consumer-visible wait) — fix the stage that dominates
            stage = "host batch production" if host_wait >= dev_wait \
                else "H2D staging"
            lines.append(f"  host batch {host_wait:.2f}s (overlapped), "
                         f"H2D staging {dev_wait:.2f}s -> "
                         f"bottleneck stage: {stage}")
    else:
        lines.append("input:      no wait/step time recorded")
    return "\n".join(lines)


def _rank_label(path, ordinal):
    """Nearest all-digit path component (launch.py's <dir>/<rank>/ layout),
    else the argument position."""
    for part in reversed(os.path.normpath(os.path.dirname(path)).split(os.sep)):
        if part.isdigit():
            return f"rank {int(part)}"
    return f"rank {ordinal}"


def _step_stats(events):
    steps = [e["dur_s"] for e in events
             if e.get("kind") == "step"
             and isinstance(e.get("dur_s"), (int, float))]
    recompiles = sum(1 for e in events if e.get("kind") == "recompile")
    return steps, recompiles


def report_merged(paths):
    """Per-file sections labelled by rank, plus the cross-rank summary:
    step counts, per-rank p99, and the slowest rank (the straggler
    candidate before reaching for tools/postmortem_report.py). Each file
    is parsed once and shared by its section and the summary."""
    labels = [_rank_label(p, i) for i, p in enumerate(paths)]
    loaded = [load(p) for p in paths]
    sections = [report(p, label=l, data=d)
                for p, l, d in zip(paths, labels, loaded)]

    lines = [f"merged summary: {len(paths)} ranks", "=" * 60]
    slowest = None
    for (events, _), label in zip(loaded, labels):
        steps, recompiles = _step_stats(events)
        if steps:
            p50, p99 = percentile(steps, 50), percentile(steps, 99)
            lines.append(f"  {label}: {len(steps)} steps  "
                         f"p50 {p50 * 1e3:.2f} ms  p99 {p99 * 1e3:.2f} ms  "
                         f"{recompiles} recompiles")
            if slowest is None or p99 > slowest[1]:
                slowest = (label, p99)
        else:
            lines.append(f"  {label}: no step events  "
                         f"{recompiles} recompiles")
    if slowest is not None and len(paths) > 1:
        lines.append(f"  slowest by p99: {slowest[0]} "
                     f"({slowest[1] * 1e3:.2f} ms)")
    return "\n\n".join(sections + ["\n".join(lines)])


def main(argv):
    if len(argv) >= 2 and argv[1] in ("-h", "--help"):
        print(__doc__.strip())
        return 0
    if len(argv) < 2:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    if len(argv) == 2:
        print(report(argv[1]))
    else:
        print(report_merged(argv[1:]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
