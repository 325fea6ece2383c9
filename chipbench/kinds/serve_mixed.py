"""kind `serve_mixed`: the closed loop of kind `serve` over a model whose
layers keep different rows (`mxnet_tpu.models.laguna`: window layers beside
full ones, in two page classes), under prompts of very different lengths
through one bucket.

Nothing is shared between requests: every prompt's ids are drawn from
`--seed`, the (prompt, new tokens) pairs are the traffic file's cycle in
order of submission, so the composition of scheduler step k is a function
of the cell's files, as in kind `serve` (whose `Client`, `ClosedLoop` and
window protocol, closed by counts, this imports).

`correct` is decided at the timed sizes from what the timed path itself
produced: the server keeps, for the audited requests (one long, one
short), the float32 logits row behind every token it emitted
(`submit(keep_logits=True)`), and after the window they are compared with
the plain reference's full forward pass (`chipbench/reference/laguna.py`)
over prompt + generated tokens. Logits, not tokens. The invariants of kind
`serve` (every request DONE, exact length, ids in range, nothing shed)
hold beside it.
"""
import bisect
import collections
import gc
import time

import numpy as np

from chipbench import program_spans, xplane
from chipbench.kinds import serve
from chipbench.kinds.serve import Client, ClosedLoop

FED = ("attn_tokens", "attn_ctx_tokens", "attn_window_tokens",
       "chunk_steps", "token_steps", "window_pages_freed")


def model_config(config):
    """The constructor's arguments from the configuration file's own keys:
    the published names as run."""
    from mxnet_tpu.models import laguna
    keys = {k: config[k] for k in laguna.LAGUNA_XS2_PUBLISHED if k in config}
    return laguna.laguna_config(dtype=config["model"]["dtype"], **keys)


def build(ctx):
    """(server, model, model config) on a one-device mesh."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel, serve
    from mxnet_tpu.models import laguna

    cfg = model_config(ctx.config)
    parallel.make_mesh(devices=ctx.devices[:1])
    model = laguna.LagunaForCausalLM(cfg)
    mx.random.seed(ctx.seed)
    model.initialize()
    return serve.Server(model, **ctx.config["server"]), model, cfg


class MixedLoop(ClosedLoop):
    """`ClosedLoop` that keeps the audited requests' logits and, per step,
    the server's position counters, each page class's pages in use and
    what the decoding rows read."""

    def __init__(self, srv, cycle, clients, vocab, rng, audited, window):
        self.audited, self.window = set(audited), window
        self.fed = []           # per step: the server's counters, summed
        self.class_pages = []   # per step: (full, window) pages in use
        self.decode_ctx = []    # per step: (sum of the decoding rows'
        #                         contexts, of those cut to the window)
        super().__init__(srv, cycle, clients, vocab, rng)

    def submit(self):
        k = len(self.requests)
        n_prompt, n_new = self.cycle[k % len(self.cycle)]
        prompt = self.rng.randint(0, self.vocab, (n_prompt,)) \
            .astype(np.int32)
        req = self.srv.submit(prompt, max_new_tokens=n_new, eos=None,
                              temperature=0.0, keep_logits=k in self.audited)
        client = Client(req, n_prompt, n_new)
        self.requests.append(client)
        self.live.append(client)

    def step(self):
        # a request with a token feeds one row this step, its last token:
        # the context that row attends is known from lengths alone
        ctx = [c.n_prompt + len(c.req.tokens) for c in self.live
               if c.req.tokens]
        self.decode_ctx.append(
            (sum(ctx), sum(min(n, self.window) for n in ctx)))
        super().step()
        st = self.srv.stats()
        self.fed.append(tuple(st[k] for k in FED))
        self.class_pages.append(tuple(st["pages_in_use"].values()))

    def fed_over(self, steps):
        """{counter: its growth over the step range `steps`}."""
        first = self.fed[steps.start - 1] if steps.start else (0,) * len(FED)
        return {k: b - a for k, a, b
                in zip(FED, first, self.fed[steps.stop - 1])}


def audit(ctx, model, cfg, clients):
    """Compare the kept logits of the audited requests with the plain
    reference's forward pass. Returns (ok, lines to say, {name: [number
    compared, its limit]})."""
    from chipbench.reference import laguna as reference
    from chipbench.reference.glm5 import relative_errors

    spec, limits = ctx.config["audit"], ctx.config["audit"]["limits"]
    layers, top = model.layer_weights()
    full = cfg["rope_parameters"]["full_attention"]
    ok, lines, checks = True, [], {}
    for n, client in enumerate(clients):
        req = client.req
        seq = np.concatenate([req.prompt, req.tokens[:-1]])
        got = np.stack(req.logits)

        def against(keys=None, **kw):
            return np.asarray(reference.forward(
                seq, layers, top, dict(cfg, **(keys or {})),
                logits_from=req.prompt.size - 1, block=spec["block"], **kw))

        t = time.perf_counter()
        want = against()
        err = relative_errors(got, want)
        seconds = time.perf_counter() - t
        med, worst = float(np.median(err)), float(err.max())
        passed = got.shape == want.shape and bool(np.isfinite(got).all()) \
            and med <= limits["median_relative_error"] \
            and worst <= limits["max_relative_error"]
        ok = ok and passed
        checks[f"audit{n}_median_relative_error"] = [
            med, limits["median_relative_error"]]
        checks[f"audit{n}_max_relative_error"] = [
            worst, limits["max_relative_error"]]
        lines.append(
            f"audit request {req.id}: {got.shape[0]} positions x "
            f"{got.shape[1]} logits at contexts {req.prompt.size}-"
            f"{seq.size}; relative error median {med:.6f} (limit "
            f"{limits['median_relative_error']}), 90th percentile "
            f"{float(np.percentile(err, 90)):.6f}, largest {worst:.6f} "
            f"(limit {limits['max_relative_error']}); greedy token equal "
            f"at {float((got.argmax(-1) == want.argmax(-1)).mean()):.4f} "
            f"of positions; reference {seconds:.1f}s: "
            f"{'ok' if passed else 'NOT CORRECT'}")
        if n == 0 and ctx.trace:
            # the readings the limits were set between (PERF.md), against
            # the same float32 reference; the traced run alone pays them
            for label, kw in (
                    ("operands at bf16's mantissa", {"mantissa_bits": 7}),
                    ("operands at an fp8 mantissa (3 bits)",
                     {"mantissa_bits": 3}),
                    ("the sliding layers attending everything",
                     {"keys": {"sliding_window": seq.size + 1}}),
                    ("the full layers' RoPE on all of the head",
                     {"keys": {"rope_parameters": dict(
                         cfg["rope_parameters"], full_attention=dict(
                             full, partial_rotary_factor=1))}}),
                    ("query head h reading KV head h % "
                     f"{cfg['num_key_value_heads']}",
                     {"group_interleaved": True})):
                e = relative_errors(against(**kw), want)
                lines.append(
                    f"  reference with {label}: median "
                    f"{float(np.median(e)):.6f}, 90th percentile "
                    f"{float(np.percentile(e, 90)):.6f}, largest "
                    f"{float(e.max()):.6f}")
    return ok, lines, checks


SCOPES = ("full_attention", "window_attention", "kv_arena_update",
          "moe_experts", "lm_head")
LABEL = "serve.paged/bucket={bucket}/chunk={chunk}"


def scope_lines(trace, steps):
    """Where the traced stretch's device time went, by the program's named
    scopes and under each by operation, with the readers' own clock join
    (`readers/trace_scope_ms_per_step`): lines to say, ms per step. Also
    what the join leaves out: events outside every `serve.decode_step` span
    and events whose instruction the executable's scope map does not hold."""
    found = program_spans.in_stretch({"trace": trace}, "serve.step")
    if not found or not trace.devices:
        return []
    spans, offset = found
    windows = sorted(
        (program_spans.start_ns(s) + offset, program_spans.end_ns(s) + offset,
         LABEL.format(**s)) for s in spans if s["name"] == "serve.decode_step")
    maps = {lab: program_spans.scope_map(lab) for lab in {w[2] for w in windows}}
    starts = [w[0] for w in windows]
    by = collections.defaultdict(collections.Counter)
    for events in trace.devices.values():
        for op, start, ns in program_spans.self_time_events(events):
            k = bisect.bisect_right(starts, start) - 1
            if k < 0 or start > windows[k][1]:
                by["outside every serve.decode_step"][xplane.base_name(op)] += ns
                continue
            path = maps[windows[k][2]].get(op)
            if path is None:
                by["not in the scope map"][xplane.base_name(op)] += ns
                continue
            under = [sc for sc in SCOPES if program_spans.in_scopes(path, {sc})]
            by[under[0] if under else "under no scope"][
                xplane.base_name(op)] += ns
    per = 1e6 * steps * len(trace.devices)
    return [f"  {scope}: {sum(ops.values()) / per:.3f} ms a step (" + ", ".join(
        f"{op} {ns / per:.3f}" for op, ns in ops.most_common(4)) + ")"
        for scope, ops in sorted(by.items(), key=lambda e: -sum(e[1].values()))]


def stretch_counts(step_fed, decode_ctx, emitted):
    """What the traced stretch fed, for `work_window`: the growth of the
    server's position counters over it, the decoding rows' contexts (whole
    and cut to the window), and the tokens it gave back."""
    def over(key):
        return sum(f[key] for f in step_fed)

    return {
        "steps": len(step_fed), "tokens": over("attn_tokens"),
        "ctx_tokens": over("attn_ctx_tokens"),
        "window_tokens": over("attn_window_tokens"),
        "passes": over("chunk_steps") + over("token_steps"),
        "decode_ctx_tokens": sum(c for c, _ in decode_ctx),
        "decode_window_tokens": sum(w for _, w in decode_ctx),
        "emitted": emitted}


def run(ctx):
    from chipbench import window

    traffic, server_args = ctx.traffic, ctx.config["server"]
    t_build = time.perf_counter()
    srv, model, cfg = build(ctx)
    t_model = time.perf_counter()
    slots, page = server_args["slots"], server_args["page_size"]
    cycle = [tuple(pair) for pair in traffic["cycle"]]
    n_l = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:n_l]
    heads = cfg["num_attention_heads_per_layer"][:n_l]
    ctx.say(f"{ctx.cell['name']}: Laguna {n_l}L/{cfg['hidden_size']} "
            f"{cfg['dtype']}, layers " + ", ".join(
                f"{k.split('_')[0]}:{h}" for k, h in zip(kinds, heads))
            + f" query heads over {cfg['num_key_value_heads']} KV heads, "
            f"window {cfg['sliding_window']}, {cfg['num_experts']} experts "
            f"({cfg['num_experts_per_tok']} a token), vocabulary "
            f"{cfg['vocab_size']}; Server({server_args}); "
            f"{traffic['clients']} clients in a closed loop over a cycle "
            f"of {len(cycle)} (prompt, new) pairs, no shared prefix")

    loop = MixedLoop(srv, cycle, traffic["clients"], cfg["vocab_size"],
                     np.random.RandomState(ctx.seed), traffic["audited"],
                     cfg["sliding_window"])
    win = window.measure(ctx, loop.step, lambda: None,
                         traffic["warmup_steps"], traffic["trace_steps"],
                         serve.by_count(ctx, loop))
    audited = [loop.requests[k] for k in traffic["audited"]]
    drained = 0     # outside every measurement: the audit needs its ends
    while not all(c.req.done for c in audited):
        if drained >= traffic["audit_drain_steps"]:
            raise RuntimeError("the audited requests did not finish")
        loop.step()
        drained += 1
    st = srv.stats()        # before stop() cancels what is in flight
    srv.stop()
    ctx.say(f"set-up {win.setup_s:.1f}s: imports and device "
            f"{t_build - ctx.t_start:.1f}, model and server "
            f"{t_model - t_build:.1f}, warm-up steps "
            f"{win.t0 - t_model:.1f}; {drained} steps after the "
            "measurements until the audited requests ended")
    end_to_end, in_window, held = serve.window_numbers(
        ctx, loop, win, f"; executables {st['executables']}; window pages "
        f"returned {st['window_pages_freed']}")
    steps = win.steps
    traced = slice(in_window.stop, in_window.stop + win.traced_steps)
    full_pages = [f for f, _ in loop.class_pages[in_window]]
    window_pages = [w for _, w in loop.class_pages[in_window]]
    ctx.say(f"pages in use over the window, mean: full class "
            f"{sum(full_pages) / steps:.1f} of {st['pool_pages_total']}, "
            f"window class {sum(window_pages) / steps:.1f}; passes "
            f"{loop.fed_over(in_window)['chunk_steps']} wide and "
            f"{loop.fed_over(in_window)['token_steps']} narrow")

    # the pool's arenas go before the reference's temporaries come
    loop.srv = srv = None
    gc.collect()
    agrees, lines, checks = audit(ctx, model, cfg, audited)
    for line in lines:
        ctx.say(line)

    full_heads = [h for k, h in zip(kinds, heads) if k == "full_attention"]
    window_heads = [h for k, h in zip(kinds, heads) if k != "full_attention"]
    shapes = {"layers": n_l, "slots": slots, "page_size": page,
              "full_layer_heads": full_heads,
              "window_layer_heads": window_heads,
              "kv_heads": cfg["num_key_value_heads"],
              "head_dim": cfg["head_dim"], "window": cfg["sliding_window"],
              "prefill_chunk": server_args["prefill_chunk"], "itemsize": 2,
              # the rest of what `work_window.serve_step` reads
              "hidden": cfg["hidden_size"],
              "dense_layers": cfg["mlp_layer_types"][:n_l].count("dense"),
              "dense_width": cfg["intermediate_size"],
              "expert_width": cfg["moe_intermediate_size"],
              "shared_width": cfg["shared_expert_intermediate_size"],
              "experts_per_token": cfg["num_experts_per_tok"],
              "experts": cfg["num_experts"], "vocab": cfg["vocab_size"]}
    if win.traced_steps:
        step_fed = [loop.fed_over(slice(k, k + 1))
                    for k in range(traced.start, traced.stop)]
        shapes["traced"] = stretch_counts(
            step_fed, loop.decode_ctx[traced], sum(loop.tokens_out[traced]))
        ctx.say(f"traced stretch: {shapes['traced']}; per step (wide "
                "passes, narrow passes): " + ", ".join(
                    f"({f['chunk_steps']}, {f['token_steps']})"
                    for f in step_fed))
        ctx.say("device time of the stretch by scope:")
        for line in scope_lines(win.recording.trace, win.traced_steps):
            ctx.say(line)
    lost = st["rejected"] + st["shed"] + st["failed"] + st["expired"]
    return {
        "correct": agrees and not loop.failures and lost == 0
        and loop.checked > 0 and not win.overran,
        "attempted": loop.checked,
        "failed": len(loop.failures) + lost + win.overran,
        "checks": dict(checks,
                       requests_not_as_asked=[len(loop.failures), 0],
                       requests_lost=[lost, 0],
                       window_overran=[int(win.overran), 0]),
        "end_to_end": end_to_end,
        "window": held,
        "spans": {"bench.step": loop.step_s[in_window]},
        "counters": {
            "steps": steps,
            "prefill_steps": sum(loop.prefill_steps[in_window]),
            "running_sum": sum(loop.running[in_window]),
            "slot_steps": slots * steps,
            "pages_in_use_sum": sum(loop.pages_in_use[in_window]),
            "page_steps": st["pool_pages_total"] * steps,
            "full_class_pages_sum": sum(full_pages),
            "window_class_pages_sum": sum(window_pages)},
        "composition": loop.composition[in_window],
        "shapes": shapes,
        "peaks": ctx.peaks,
        **window.trace_result(win),
    }
